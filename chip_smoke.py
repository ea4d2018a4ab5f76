"""Run the simulator's main path once on a TPU chip, and check what it gives.

    python chip_smoke.py             # one chip: Figure 2 grid + fleet
    python chip_smoke.py --chips 4   # the sharded grid and fleet on four
                                     # chips, each against one chip

One process holds the chip for the whole run; nothing is started beside it.

Grid phase: the full paper Figure 2 grid (3 testbeds x 4 datasets x 6
tools = 72 cells at dt=0.1 with the ``budget_for`` horizons) through
``Experiment.run`` -> ``api.sweep`` on one chip, plus one unbatched
``api.run``.  Every cell must complete and agree with the ``reference``
executor run on the host CPU in this process: ``completed`` cell for cell,
``time_s`` within one dt, energy and moved MB within ``REL_TOL``.  On the
chip, ``blocked`` and ``reference`` must agree bit for bit on the
Chameleon slice (the repo's executor invariant), and the one-lane
``api.run`` bit for bit with the same cell inside its 4-lane sweep group.

Fleet phase: ``run_fleet_online`` over the 10,000-transfer Poisson trace of
``benchmarks/fleet.py`` (0.8/s, seed 1810, 8 hosts) at dt=0.1 with 15 s
waves.  Every transfer must complete, the attempt ledger must move exactly
the offered MB (and the engine's byte counters within ``REL_TOL``), and a
1,000-transfer prefix run on the chip and on the host CPU must agree on
the completed set and on totals within ``REL_TOL``.  On the chip, the
``reference`` wave executor (the pytree scan) must give that prefix the
same bits as ``blocked`` (the flat-row default), and with NICs that never
bind, waves of two lanes (two-slot pools) the same answers as waves as
wide as the occupancy.

``--chips 4`` runs only the paths that exist across chips: the grid
through the sharded sweep runner (its 4-lane groups run one lane per
chip) and the whole fleet trace through ``run_fleet(devices=...)`` with
its waves sharded over the chips, each compared bit for bit with the same
run on one chip of the machine.

Each phase prints one JSON line (device kind, compile and wall seconds,
cells or transfers, largest deviation).  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero and prints no result; there is no
CPU fallback.  Compiled programs persist in the cache that
``benchmarks.common.use_compile_cache`` selects.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

DT = 0.1             # the paper's tick
WAVE_S = 15.0        # fleet scheduling quantum
PREFIX = 1_000       # transfers in the chip-vs-CPU fleet comparison
REL_TOL = 1e-3       # chip vs host CPU: energy, moved MB, fleet totals
SOLO_CELL = "chameleon/mixed/EEMT"   # the grid cell also run via api.run
GRID_METRICS = ("completed", "time_s", "energy_j", "avg_tput_MBps",
                "avg_power_w")


def compile_clock() -> float:
    """Seconds JAX has spent in this process tracing, lowering and
    compiling (or fetching compiled programs from the persistent cache),
    as ``repro.obs`` counts them from JAX's own compile events."""
    from repro import obs
    return sum(obs.counters()["compile_s"].values())


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


def report(phase: dict) -> None:
    print(json.dumps(phase), flush=True)


def rel_dev(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-12)


def _scalars(r) -> dict:
    d = {m: float(getattr(r, m)) for m in GRID_METRICS}
    d["moved_mb"] = float(r.avg_tput_MBps * r.time_s)
    return d


def _recording_sweep(api, devices, into: list):
    def sweeper(scenarios):
        out = api.sweep(scenarios, devices=devices)
        into.extend(out)
        return out
    return sweeper


def _completion_tick(r) -> int:
    """The tick during which a transfer drained, -1 if it did not: where
    ``api.run``'s ``done`` trace first turns True, or, for a sweep result
    (which carries no traces), the tick its ``time_s`` ends."""
    if not r.completed:
        return -1
    if r.metrics is not None:
        return int(np.argmax(r.metrics.done))
    return round(r.time_s / DT) - 1


def _same_bits(a, b) -> bool:
    """Two TransferResults agree bit for bit, completion tick included."""
    return (_scalars(a) == _scalars(b)
            and _completion_tick(a) == _completion_tick(b))


def _compare_to_cpu(name: str, got: dict, want: dict) -> float:
    """Chip vs host-CPU reference for one cell; returns the largest
    relative deviation of energy and moved MB."""
    check(got["completed"] == want["completed"],
          f"{name}: completed {got['completed']} on the chip, "
          f"{want['completed']} on the CPU")
    check(abs(got["time_s"] - want["time_s"]) <= DT * (1 + 1e-9),
          f"{name}: time_s {got['time_s']} on the chip vs "
          f"{want['time_s']} on the CPU (more than one dt apart)")
    dev = max(rel_dev(got["energy_j"], want["energy_j"]),
              rel_dev(got["moved_mb"], want["moved_mb"]))
    check(dev <= REL_TOL, f"{name}: energy/moved MB off by {dev:.3g} "
                          f"relative (> {REL_TOL})")
    return dev


def grid_phase(jax, api, chip, cpu, exp) -> dict:
    cells = exp.cells()
    scenarios = [c.scenario for c in cells]
    names = ["/".join(c.labels[a] for a in exp.axis_names) for c in cells]

    results: list = []
    c0, t0 = compile_clock(), time.perf_counter()
    report = exp.run(cells=cells,
                     sweeper=_recording_sweep(api, (chip,), results))
    wall_s = time.perf_counter() - t0
    compile_s = compile_clock() - c0
    check(len(results) == len(cells) == len(report),
          f"{len(results)} results for {len(cells)} cells")
    incomplete = [n for n, r in zip(names, results) if not r.completed]
    check(not incomplete, f"cells did not complete: {incomplete}")

    # Host-CPU reference: the plain pytree scan, same scenarios.
    with jax.default_device(cpu):
        ref = api.sweep([dataclasses.replace(sc, executor="reference")
                         for sc in scenarios], devices=(cpu,))
    chip_rows = [_scalars(r) for r in results]
    ref_rows = [_scalars(r) for r in ref]
    max_dev = max(_compare_to_cpu(n, g, w)
                  for n, g, w in zip(names, chip_rows, ref_rows))

    # One unbatched run through api.run (its own one-lane executable):
    # within tolerance of the CPU, and bit for bit the lane it had in its
    # sweep group.
    one = names.index(SOLO_CELL)
    t1 = time.perf_counter()
    solo_res = api.run(scenarios[one])
    run_wall_s = time.perf_counter() - t1
    solo = _scalars(solo_res)
    max_dev = max(max_dev, _compare_to_cpu(f"api.run {names[one]}", solo,
                                           ref_rows[one]))
    check(_same_bits(solo_res, results[one]),
          f"api.run {names[one]} {solo} differs from its sweep-group lane "
          f"{chip_rows[one]} on the chip")

    # On the chip, blocked (the default) and reference are bit-identical.
    slice_idx = [i for i, c in enumerate(cells)
                 if c.labels["testbed"] == "chameleon"]
    on_chip_ref = api.sweep([dataclasses.replace(scenarios[i],
                                                 executor="reference")
                             for i in slice_idx], devices=(chip,))
    for i, r in zip(slice_idx, on_chip_ref):
        check(_scalars(r) == chip_rows[i],
              f"{names[i]}: blocked {chip_rows[i]} != reference "
              f"{_scalars(r)} on the chip")

    return {"phase": "grid", "device_kind": chip.device_kind,
            "cells": len(cells), "compile_s": compile_s, "wall_s": wall_s,
            "api_run_wall_s": run_wall_s,
            "max_rel_dev_vs_cpu": max_dev,
            "blocked_eq_reference_cells": len(slice_idx)}


def _fleet_run(fleet, trace, hosts, capacity: int, **kw):
    return fleet.run_fleet_online(trace, hosts, wave_s=WAVE_S, dt=DT,
                                  pool_capacity=capacity, **kw)


def fleet_phase(jax, fleet, faults_mod, chip, cpu, trace,
                hosts) -> dict:
    # The host slot budgets bound the in-flight population, so a pool of
    # that capacity never makes a request wait for a slot.
    check(all(h.slots > 0 for h in hosts), "hosts need slot budgets")
    capacity = sum(h.slots for h in hosts)
    offered = math.fsum(d.total_mb for r in trace for d in r.datasets)

    c0, t0 = compile_clock(), time.perf_counter()
    with jax.default_device(chip):
        rep = _fleet_run(fleet, trace, hosts, capacity,
                         faults=faults_mod.FaultSchedule())
    wall_s = time.perf_counter() - t0
    compile_s = compile_clock() - c0
    check(rep.fold.transfers == len(trace) and rep.dropped == 0,
          f"{rep.fold.transfers} of {len(trace)} transfers retired, "
          f"{rep.dropped} dropped")
    check(rep.completed == len(trace),
          f"{len(trace) - rep.completed} transfers did not complete")
    check(rep.counters["peak_in_flight"] <= capacity,
          f"in-flight peak {rep.counters['peak_in_flight']} > {capacity}")
    churn = rep.churn
    check(churn["goodput_mb"] == churn["offered_mb"] == offered,
          f"ledger moved {churn['goodput_mb']} MB of {offered} offered")
    counter_dev = rel_dev(rep.total_gb * 1024.0, offered)
    check(counter_dev <= REL_TOL,
          f"engine byte counters moved {rep.total_gb * 1024.0} MB of "
          f"{offered} offered ({counter_dev:.3g} relative)")

    prefix = trace[:PREFIX]
    with jax.default_device(chip):
        on_chip = _fleet_run(fleet, prefix, hosts, capacity,
                             track_transfers=True)
    with jax.default_device(cpu):
        on_cpu = _fleet_run(fleet, prefix, hosts, capacity,
                            track_transfers=True)
    done_chip = {t.name for t in on_chip.transfers if t.completed}
    done_cpu = {t.name for t in on_cpu.transfers if t.completed}
    check(done_chip == done_cpu,
          f"completed sets differ: {sorted(done_chip ^ done_cpu)[:10]}")
    prefix_dev = max(
        rel_dev(on_chip.total_energy_j, on_cpu.total_energy_j),
        rel_dev(on_chip.total_gb, on_cpu.total_gb))
    check(prefix_dev <= REL_TOL,
          f"{PREFIX}-transfer prefix totals off by {prefix_dev:.3g} "
          f"relative (> {REL_TOL})")

    # The two wave lowerings agree bit for bit on the chip: the pytree
    # reference scan against the flat blocked rows.
    t1 = time.perf_counter()
    with jax.default_device(chip):
        reference = _fleet_run(fleet, prefix, hosts, capacity,
                               executor="reference", track_transfers=True)
    reference_wall_s = time.perf_counter() - t1
    _check_same_transfers("reference executor", "blocked executor",
                          reference, on_chip)

    # The wave width does not move a lane's bits on the chip.  With NICs
    # that never bind, no answer depends on who shares a wave; the prefix
    # runs once with pools that hold every lane (waves as wide as the
    # occupancy) and once with two slots a pool (every wave two lanes,
    # the rest queued, so start times move but no answer may).
    free = tuple(dataclasses.replace(h, nic_mbps=1e9) for h in hosts)
    lanes: list = []                  # lanes a wave ran, over all pools
    with jax.default_device(chip):
        wide = _fleet_run(fleet, prefix, free, capacity,
                          track_transfers=True,
                          on_wave=lambda w: lanes.append(w["lanes"]))
        narrow = _fleet_run(fleet, prefix, free, 2, track_transfers=True)
    check(narrow.counters["peak_queue_depth"] > 0,
          "the two-slot pools never queued a request")
    answers = {t.name: (t.completed, t.time_s, t.energy_j, t.moved_mb)
               for t in wide.transfers}
    differ = [t.name for t in narrow.transfers
              if answers.get(t.name) != (t.completed, t.time_s, t.energy_j,
                                         t.moved_mb)]
    check(not differ and len(answers) == len(narrow.transfers),
          f"two-lane waves differ from occupancy-wide ones (up to "
          f"{max(lanes)} lanes a wave) in {len(differ)} of {len(answers)} "
          f"transfers: {differ[:4]}")

    return {"phase": "fleet", "device_kind": chip.device_kind,
            "transfers": len(trace), "hosts": len(hosts),
            "pool_capacity": capacity,
            "peak_in_flight": rep.counters["peak_in_flight"],
            "lanes_run": rep.counters["lanes_run"],
            "waves": rep.waves, "compile_s": compile_s, "wall_s": wall_s,
            "offered_mb": offered, "ledger_goodput_mb": churn["goodput_mb"],
            "counter_rel_dev": counter_dev,
            "prefix_transfers": len(prefix),
            "prefix_max_rel_dev_vs_cpu": prefix_dev,
            "prefix_reference_wall_s": reference_wall_s,
            "prefix_reference_eq_blocked": True,
            "prefix_wide_peak_lanes": max(lanes),
            "prefix_two_lane_waves_eq_wide": True}


def _check_same_transfers(what: str, against: str, a, b) -> None:
    """Two fleet reports agree bit for bit, transfer by transfer."""
    by_name = {t.name: t for t in b.transfers}
    differ = [(t, by_name.get(t.name)) for t in a.transfers
              if by_name.get(t.name) != t]
    check(not differ and len(a.transfers) == len(b.transfers)
          and a.total_energy_j == b.total_energy_j
          and a.total_gb == b.total_gb,
          f"{what} differs from the {against} in {len(differ)} of "
          f"{len(a.transfers)} transfers (energy total off by "
          f"{rel_dev(a.total_energy_j, b.total_energy_j):.3g}); first "
          f"({what}, {against}): {differ[:2]}")


def grid_sharded_phase(api, chips, exp) -> dict:
    """The grid through the sharded sweep runner, bit for bit against one
    chip."""
    cells = exp.cells()
    multi: list = []
    c0, t0 = compile_clock(), time.perf_counter()
    exp.run(cells=cells, sweeper=_recording_sweep(api, chips, multi))
    wall_s = time.perf_counter() - t0
    compile_s = compile_clock() - c0
    single: list = []
    exp.run(cells=cells, sweeper=_recording_sweep(api, chips[:1], single))
    check(all(r.completed for r in multi), "sharded grid: a cell did not "
                                           "complete")
    differ = [(c.labels, rel_dev(m.energy_j, s.energy_j))
              for c, m, s in zip(cells, multi, single)
              if not _same_bits(m, s)]
    check(not differ, f"sharded grid differs from one chip in "
                      f"{len(differ)} cells (cell, energy deviation): "
                      f"{differ[:4]}")
    return {"phase": "grid_sharded", "device_kind": chips[0].device_kind,
            "chips": len(chips), "cells": len(cells),
            "compile_s": compile_s, "wall_s": wall_s,
            "max_rel_dev_vs_one_chip": 0.0}


def fleet_sharded_phase(fleet, chips, trace, hosts) -> dict:
    """The fleet with its waves sharded over the chips, bit for bit
    against one chip."""
    c0, t0 = compile_clock(), time.perf_counter()
    sharded = fleet.run_fleet(trace, hosts, wave_s=WAVE_S, dt=DT,
                              devices=chips)
    wall_s = time.perf_counter() - t0
    compile_s = compile_clock() - c0
    one = fleet.run_fleet(trace, hosts, wave_s=WAVE_S, dt=DT,
                          devices=chips[:1])
    check(sharded.completed == len(trace),
          f"sharded fleet: {len(trace) - sharded.completed} transfers did "
          f"not complete")
    _check_same_transfers("sharded fleet", "one chip", sharded, one)
    return {"phase": "fleet_sharded", "device_kind": chips[0].device_kind,
            "chips": len(chips), "transfers": len(trace),
            "compile_s": compile_s, "wall_s": wall_s,
            "max_rel_dev_vs_one_chip": 0.0}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded grid and fleet on four "
                         "chips, each against one chip")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir() or \
            not (ROOT / "benchmarks").is_dir():
        raise SystemExit(f"chip_smoke: {ROOT} does not hold the repository "
                         f"(src/repro and benchmarks/ are missing)")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    # The host CPU runs the reference; keep its backend beside the TPU.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:       # a named platform failed to start
        raise SystemExit(f"chip_smoke: no TPU chip found: {e}")
    if devices[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU chip found (JAX sees "
                         f"{devices[0].platform!r}); there is no CPU "
                         f"fallback")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} chip(s)")
    chips = tuple(devices[:args.chips])
    cpu = jax.devices("cpu")[0]

    from benchmarks import fig2
    from benchmarks import fleet as fleet_bench
    from benchmarks.common import use_compile_cache
    from repro import api, fleet
    from repro.workloads import faults

    use_compile_cache()
    exp = fig2.experiment(smoke=False)
    trace, hosts = fleet_bench.build(smoke=False)

    if args.chips == 1:
        report(grid_phase(jax, api, chips[0], cpu, exp))
        report(fleet_phase(jax, fleet, faults, chips[0], cpu, trace,
                           hosts))
    else:
        report(grid_sharded_phase(api, chips, exp))
        report(fleet_sharded_phase(fleet, chips, trace, hosts))
    print(json.dumps({"ok": True, "device": {
        "platform": chips[0].platform, "kind": chips[0].device_kind,
        "count": len(chips)}}))


if __name__ == "__main__":
    main()
