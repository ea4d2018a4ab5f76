"""Streaming wave scheduler: thousands of transfers through one engine.

``run_fleet`` executes an arrival trace against a host pool in *waves* of
``wave_s`` simulated seconds:

1. **Admit.**  Arrivals whose time has come are assigned to hosts (pinned,
   least-loaded, or round-robin) subject to each host's transfer-slot
   budget; the rest queue FIFO.  Admission state (``ScanInputs``, initial
   ``SimState``/``TunerState``) is built once per unique
   (controller, datasets, profile, cpu) combination and shared across the
   trace — menu-based traces prepare dozens of combos, not thousands.
2. **Rescale.**  Per host, if the per-flow bandwidth demands of its
   in-flight transfers exceed the NIC, every transfer on that host gets its
   available bandwidth scaled by ``nic / demand`` for the coming wave
   (``ScanInputs.bw`` carries the scalar share — the engine hook).
3. **Run.**  Active lanes are grouped by (controller code, environment
   code, cpu) — exactly the ``sweep`` grouping, so a heterogeneous pool
   (per-host environments, see ``repro.fleet.hosts``) compiles one wave
   runner per distinct physics — partition-padded to the trace-wide maximum
   (``repro.api.scenario.pad_partition_inputs``), stacked, padded to a
   power-of-two lane bucket with drained zero lanes
   (``repro.distributed.sharding.pad_batch(fill="zero")``) to bound
   recompiles, and advanced ``wave_steps`` ticks through the jitted,
   vmapped wave runner (``repro.core.engine.get_wave_runner``) — sharded
   across devices via ``shard_batch`` when more than one is available.
4. **Drain & refill.**  Lanes whose transfers drained (or exceeded their
   budget) are retired, their host slots freed, and the next wave admits
   from the queue.

Because the wave runner shares the engine's per-tick step function and
completion masking, a transfer that never sees contention (bandwidth share
1.0 throughout) is **bit-identical** to an independent ``api.run`` of the
same scenario — tested in tests/test_fleet.py.  All scheduling decisions
are functions of (arrival time, request content), never of trace order, so
shuffling a trace leaves every fleet number unchanged.

Lane state is held host-side as the flat ``repro.core.tickstate`` rows, so
on the default ``blocked`` executor a wave batch is five ``np.stack`` calls
(parameter rows, shares, two state rows, step indices) instead of per-lane
pytree stack/unstack traffic — which was the dominant host cost of the
fleet hot loop.  ``executor="reference"`` keeps the pytree wave contract as
the golden parity path.

The admission/rescale decisions themselves (combo preparation, host
picking, NIC shares, tick budgets, retirement records) live in
``repro.fleet.admission`` and are shared verbatim with the bounded-memory
online loop (``repro.fleet.online``) — one implementation, two drivers.
"""
from __future__ import annotations

import dataclasses
import math
from collections import defaultdict
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core import engine, tickstate

from .admission import (Combo, budget_steps, combo_key, make_transfer,
                        nic_shares, pick_host, resume_request)
from .aggregates import FleetReport, FleetTransfer, HostStats
from .arrivals import TransferRequest, request_sort_key
from .hosts import Host


@dataclasses.dataclass
class _Lane:
    """One in-flight transfer (mutable host-side bookkeeping).

    The engine carry lives as the two flat ``TickLayout`` rows — stacking a
    wave batch is a handful of ``np.stack`` calls instead of per-lane
    pytree traffic, which was the fleet hot loop's dominant host cost."""

    seq: int                       # admission order (stable report order)
    req: TransferRequest
    host_idx: int
    combo: Combo
    st_f32: np.ndarray             # flat f32 state row (TickLayout)
    st_i32: np.ndarray             # flat i32 state row (TickLayout)
    start_s: float
    budget_steps: int
    steps_done: int = 0
    done_at: int = -1


def _stack(trees):
    return jax.tree.map(lambda *xs: np.stack(xs), *trees)


def _run_wave_group(key, lanes: list, shares: list, wave_steps: int,
                    dt: float, devices, lay: tickstate.TickLayout,
                    executor: str) -> None:
    """Advance one controller-code group of lanes by one wave, in place.

    On the ``blocked`` executor (the default resolution) a wave batch is
    five ``np.stack``/``np.asarray`` calls over the lanes' flat rows; the
    ``reference`` executor is the parity path — it unpacks the rows into
    the pytree wave contract (batched, pure numpy slicing) and repacks per
    lane afterwards, bit-identical by construction.
    """
    from repro.distributed import sharding as shd

    code, env_code, cpu, ctrl_every = key
    n = len(lanes)
    step0 = np.asarray([ln.steps_done for ln in lanes], np.int32)
    f32 = np.stack([ln.st_f32 for ln in lanes])
    i32 = np.stack([ln.st_i32 for ln in lanes])
    if executor == "blocked":
        batch = (
            np.stack([ln.combo.params_row for ln in lanes]),
            np.asarray(shares, np.float32),
            f32, i32, step0,
        )
    else:
        sim, ts = lay.unpack_state(f32, i32)
        batch = (
            _stack([ln.combo.inputs._replace(bw=np.float32(s))
                    for ln, s in zip(lanes, shares)]),
            sim, ts, step0,
        )
    # Power-of-two lane buckets bound the number of distinct compiled
    # shapes per group to O(log max_concurrency); the filler lanes are
    # zeroed, i.e. born drained, and cost nothing.
    bucket = 1 << max(n - 1, 0).bit_length()
    n_parts = lay.n_partitions if executor == "blocked" else None
    if shd.should_shard(n, devices):
        bucket = -(-bucket // len(devices)) * len(devices)
        batch, _ = shd.pad_batch(batch, bucket, fill="zero")
        mesh = shd.batch_mesh(devices)
        runner = engine.get_sharded_wave_runner(
            code, env_code, cpu, wave_steps, dt, ctrl_every, tuple(devices),
            executor=executor, n_partitions=n_parts)
        out = runner(*shd.shard_batch(batch, mesh))
    else:
        batch, _ = shd.pad_batch(batch, bucket, fill="zero")
        runner = engine.get_wave_runner(code, env_code, cpu, wave_steps, dt,
                                        ctrl_every, executor=executor,
                                        n_partitions=n_parts)
        out = runner(*batch)
    if executor == "blocked":
        f32o, i32o, done_at = (np.asarray(x) for x in out)
        for b, ln in enumerate(lanes):
            ln.st_f32 = f32o[b]
            ln.st_i32 = i32o[b]
            ln.steps_done += wave_steps
            if ln.done_at < 0:
                ln.done_at = int(done_at[b])
    else:
        sim, ts, done_at = out
        sim = jax.tree.map(np.asarray, sim)
        ts = jax.tree.map(np.asarray, ts)
        done_at = np.asarray(done_at)
        for b, ln in enumerate(lanes):
            ln.st_f32, ln.st_i32 = lay.pack_state(
                jax.tree.map(lambda x: x[b], sim),
                jax.tree.map(lambda x: x[b], ts), xp=np)
            ln.steps_done += wave_steps
            if ln.done_at < 0:
                ln.done_at = int(done_at[b])


def run_fleet(trace: Sequence[TransferRequest], hosts: Sequence[Host], *,
              wave_s: float = 30.0, dt: float = 0.1,
              horizon_s: Optional[float] = None,
              assignment: str = "least-loaded",
              devices: Optional[Sequence] = None,
              executor: str = "auto",
              faults=None,
              slo_s: Optional[float] = None) -> FleetReport:
    """Run an arrival trace against a host pool; see the module docstring.

    ``wave_s`` is the scheduling quantum: admissions and bandwidth rescaling
    happen at wave boundaries (a transfer's ``total_s`` budget is quantized
    up to whole waves).  ``horizon_s`` hard-stops the simulation; by default
    the fleet runs until every transfer completes or exhausts its budget.
    ``devices`` selects accelerator devices for lane sharding (default: all
    local devices; single-device hosts use the plain vmapped runner).
    ``executor`` picks the engine lowering for the wave runners:
    ``reference`` or ``blocked`` (bit-identical; ``auto`` is ``blocked``,
    the executor the wave batching is shaped for).  ``pallas`` raises —
    the fused kernel has no wave form.

    ``faults`` injects a :class:`repro.workloads.faults.FaultSchedule`
    (or any object with its five driver methods): host-loss windows kill
    in-flight lanes and block admission, NIC-degrade windows cap the
    contention rescale, named kills requeue transfers with their remaining
    bytes (``restart="resume"``) or from scratch, and the report grows a
    ``churn`` goodput-vs-throughput block.  ``slo_s`` arms per-request
    latency SLO tracking (``latency`` percentiles + ``slo`` violation
    block on the report) — see ``repro.workloads.http``.  Both default to
    off, leaving the fault-free report bit-identical to previous releases.
    """
    hosts = tuple(hosts)
    if not hosts:
        raise ValueError("need at least one host")
    wave_steps = int(round(wave_s / dt))
    if wave_steps < 1:
        raise ValueError(f"wave_s={wave_s} shorter than dt={dt}")
    if devices is None:
        devices = jax.devices()
    executor = engine.resolve_executor(executor)

    reqs = sorted(trace, key=request_sort_key)

    # One prepared _Combo per unique admission state; the trace-wide max
    # partition count makes every lane shape-compatible.  The partition
    # count is a function of the datasets alone (Algorithm-1 chunking
    # splits files *within* partitions), so p_max from the pre-pass also
    # covers combos created later for other hosts' CPU profiles or
    # environments.
    combos: dict[tuple, Combo] = {}
    p_max = 0
    finalized = False

    def combo_for(req: TransferRequest, host: Host) -> Combo:
        ck = combo_key(req, host)
        if ck not in combos:
            c = Combo(req, host, dt)
            # Combos created after the pre-pass (an unpinned request landing
            # on a host whose (cpu, environment) no earlier combo covered)
            # finalize immediately: p_max is already trace-wide.
            if finalized:
                c.finalize(p_max)
            combos[ck] = c
        return combos[ck]

    for req in reqs:
        if req.host is not None and not 0 <= req.host < len(hosts):
            raise ValueError(f"request {req.name!r} pinned to host "
                             f"{req.host}, pool has {len(hosts)}")
        host = hosts[req.host] if req.host is not None else hosts[0]
        p_max = max(p_max, combo_for(req, host).n_partitions)
    for c in combos.values():
        c.finalize(p_max)
    finalized = True
    lay = tickstate.TickLayout(max(p_max, 1))

    lanes: list[_Lane] = []
    waiting: list[TransferRequest] = []
    results: list[FleetTransfer] = []
    active = [0] * len(hosts)
    busy_waves = [0] * len(hosts)
    moved_mb = [0.0] * len(hosts)
    peak = [0] * len(hosts)
    rr = [0]
    ai = 0
    seq = 0
    wave = 0
    waves_run = 0
    churn = faults.churn_fold() if faults is not None else None
    last_fault_s = -math.inf

    def retire(ln: _Lane) -> None:
        name = ln.req.name or f"xfer-{ln.seq}"
        rec = make_transfer(
            lay, ln.st_f32,
            name=name,
            controller=ln.combo.ctrl_name,
            host=hosts[ln.host_idx].name,
            arrival_s=ln.req.arrival_s,
            start_s=ln.start_s,
            steps_done=ln.steps_done,
            done_at=ln.done_at,
            dt=dt,
            ideal_s=ln.combo.ideal_s,
        )
        results.append(rec)
        if churn is not None:
            churn.retire(name, attempt=ln.req.attempt,
                         completed=rec.completed,
                         offered_parts=ln.combo.offered_parts,
                         remaining_parts=ln.st_f32[:lay.n_partitions],
                         energy_j=rec.energy_j)
        active[ln.host_idx] -= 1

    while lanes or waiting or ai < len(reqs):
        now = wave * wave_s
        if horizon_s is not None and now >= horizon_s:
            break
        while ai < len(reqs) and reqs[ai].arrival_s <= now:
            waiting.append(reqs[ai])
            ai += 1

        # Fault injection at the wave boundary: kill lanes on down hosts
        # and named-kill victims, requeue what remains via resume_request.
        # The online loop runs this block at the identical point of its
        # own iteration (after ingest, before admission), with victims in
        # the same name-sorted order, so requeue positions — and therefore
        # every downstream number — match bit-for-bit.
        down = frozenset()
        if faults is not None:
            down = faults.down_hosts(now, now + wave_s)
            kill_names = faults.kills_in(last_fault_s, now)
            last_fault_s = now
            victims = []
            for ln in lanes:
                name = ln.req.name or f"xfer-{ln.seq}"
                if ln.host_idx in down:
                    victims.append((name, "host", ln))
                elif name in kill_names:
                    victims.append((name, "kill", ln))
            if victims:
                victims.sort(key=lambda v: v[0])
                dead = set()
                for name, kind, ln in victims:
                    rem = ln.st_f32[:lay.n_partitions]
                    requeue = resume_request(ln.req, name, ln.combo.specs,
                                             rem, restart=faults.restart)
                    churn.kill(name, kind=kind, attempt=ln.req.attempt,
                               offered_parts=ln.combo.offered_parts,
                               remaining_parts=rem,
                               energy_j=float(lay.energy_j(ln.st_f32)),
                               requeued=requeue is not None)
                    if requeue is not None:
                        waiting.append(requeue)
                    active[ln.host_idx] -= 1
                    dead.add(id(ln))
                lanes = [ln for ln in lanes if id(ln) not in dead]

        still = []
        for req in waiting:
            h = pick_host(req, hosts, active, assignment, rr, down)
            if h is None:
                still.append(req)
                continue
            combo = combo_for(req, hosts[h])
            lanes.append(_Lane(
                seq=seq, req=req, host_idx=h, combo=combo,
                st_f32=combo.f0, st_i32=combo.i0, start_s=now,
                budget_steps=budget_steps(req, dt)))
            seq += 1
            active[h] += 1
            peak[h] = max(peak[h], active[h])
        waiting = still

        if not lanes:
            if waiting:
                # Queued but nothing admissible (fault-downed hosts, or a
                # request pinned to one): step wave by wave until a host
                # returns.  Unreachable without faults — an unadmissible
                # queue implies a full, i.e. busy, host.
                wave += 1
                continue
            # Idle gap: jump straight to the wave of the next arrival.
            wave = max(wave + 1,
                       int(math.ceil(reqs[ai].arrival_s / wave_s)))
            continue

        # Per-host NIC contention: proportional rescale when the per-flow
        # demands of a host's in-flight transfers exceed its NIC (capacity
        # capped by any fault-injected degrade window overlapping the
        # coming wave).
        demand = [0.0] * len(hosts)
        for ln in lanes:
            demand[ln.host_idx] += ln.req.profile.bandwidth_mbps
        caps = (faults.nic_caps(hosts, now, now + wave_s)
                if faults is not None else None)
        share = nic_shares(hosts, demand, caps)

        moved_before = [lay.bytes_moved(ln.st_f32) for ln in lanes]
        groups: dict[tuple, list[int]] = defaultdict(list)
        for i, ln in enumerate(lanes):
            groups[ln.combo.key].append(i)
        for key, idxs in groups.items():
            _run_wave_group(key, [lanes[i] for i in idxs],
                            [share[lanes[i].host_idx] for i in idxs],
                            wave_steps, dt, devices, lay, executor)

        hosts_active = set()
        for before, ln in zip(moved_before, lanes):
            moved_mb[ln.host_idx] += lay.bytes_moved(ln.st_f32) - before
            hosts_active.add(ln.host_idx)
        for h in hosts_active:
            busy_waves[h] += 1
        waves_run += 1

        live = []
        for ln in lanes:
            done = lay.remaining_sum(ln.st_f32) <= 0.0
            if done or ln.steps_done >= ln.budget_steps:
                retire(ln)
            else:
                live.append(ln)
        lanes = live
        wave += 1

    dropped = len(waiting) + (len(reqs) - ai)
    for ln in lanes:       # horizon cut: in-flight lanes are incomplete
        retire(ln)
    results.sort(key=lambda t: (t.start_s, t.name))

    # busy_frac is over ALL simulated waves (final `wave` spans sim_s,
    # including the idle gaps the scheduler fast-forwarded past), matching
    # the README glossary; waves_run counts only waves actually executed.
    stats = tuple(
        HostStats(
            name=h.name,
            moved_mb=float(moved_mb[i]),
            busy_frac=busy_waves[i] / max(wave, 1),
            nic_util=(moved_mb[i]
                      / max(h.nic_mbps * busy_waves[i] * wave_s, 1e-9)),
            peak_active=peak[i],
        )
        for i, h in enumerate(hosts))
    if churn is not None:
        churn.finalize()
    return FleetReport(transfers=tuple(results), host_stats=stats,
                       sim_s=wave * wave_s, waves=waves_run,
                       wave_s=wave_s, dt=dt, dropped=dropped,
                       slo_s=slo_s,
                       churn=churn.report() if churn is not None else None)
