"""Workloads benchmark: HTTP-service grids, fault churn, log-fitted paths.

    PYTHONPATH=src python -m benchmarks.workloads [--smoke]

Three legs, one per ``repro.workloads`` pillar:

1. **HTTP grid** — controllers x connection reuse x latency SLO, each cell
   a closed-loop :class:`repro.workloads.HttpService` request trace run
   through ``run_fleet``.  Rows carry the latency percentiles and
   SLO-violation rate; the wall-clock over the whole grid yields the
   ``http_requests_per_sec`` gate metric (requests simulated per second).
   Each request counts once and, with ``warm=True``, the grid has run once
   off the clock, so the figure leaves compilation out.  The committed
   baseline's figure is older: it counted every request twice (it ran
   through two fleet loops) and its clock held the wave programs the
   warm-up had not compiled, so it reads under a tenth of today's
   figure on the same machine until ``benchmarks.compare --rebaseline``.
2. **Fault churn** — a bulk trace under a seed-keyed
   :class:`repro.workloads.FaultSchedule` (host outages + NIC degrades +
   named kills).  The leg *asserts* the package's headline invariant
   before reporting: byte conservation (``goodput_mb == offered_mb``
   bit-exactly, and nothing wasted under ``restart="resume"``).
3. **Logfit grid** — an ``api.Experiment`` over a synthetic transfer log:
   fit aggregator x tool, each cell running against
   ``make_environment("logfit", ...)``.

Rows: workloads/http/<ctrl>/<reuse>/<slo>, workloads/faults/<mode>, and
the logfit grid cells; the BENCH record carries the HTTP Report (axes
controller x reuse x slo, with a ``completed`` column for the
completion-parity gate) and the logfit Report.
"""
from __future__ import annotations

import json
import math
import time

from repro import api, fleet
from repro.core.types import CHAMELEON, GB, DatasetSpec
from repro.workloads import (FaultSchedule, HttpService, KillTransfer,
                             ServiceLevel, http_request_trace)

from .common import emit

# ------------------------------------------------------------- HTTP grid --

HTTP_CONTROLLERS = ("eemt", "wget/curl")
HTTP_REUSE = {"reuse": 30.0, "cold": 0.0}
HTTP_SLOS = {"tight": 6.0, "loose": 30.0}
# Payload menu around 64 MB so a warm request is a sub-second transfer and
# the latency SLO is dominated by wave quantization + queueing — the
# regime where reuse and tuning policy actually move the violation rate.
HTTP_SERVICE = dict(request_mb=64.0, size_menu=(0.5, 1.0, 2.0),
                    conn_setup_mb=16.0, think_s=4.0, n_users=8, seed=1810)


def http_cells(smoke: bool = False):
    n_requests = 80 if smoke else 600
    for ctrl in HTTP_CONTROLLERS:
        for reuse_name, keepalive_s in HTTP_REUSE.items():
            for slo_name, slo_s in HTTP_SLOS.items():
                yield {"controller": ctrl, "reuse": reuse_name,
                       "slo": slo_name, "keepalive_s": keepalive_s,
                       "slo_s": slo_s, "n_requests": n_requests}


def run_http(smoke: bool = False) -> tuple:
    """Run the HTTP grid through the fleet; returns (Report, record)."""
    hosts = fleet.host_pool(2, nic_mbps=4.0 * CHAMELEON.bandwidth_mbps,
                            slots=0)
    rows = []
    requests = 0
    t0 = time.perf_counter()
    for cell in http_cells(smoke):
        svc = HttpService(controllers=(cell["controller"],),
                          keepalive_s=cell["keepalive_s"], **HTTP_SERVICE)
        trace = http_request_trace(svc, n_requests=cell["n_requests"])
        rep = fleet.run_fleet(trace, hosts, wave_s=5.0, dt=0.25,
                              slo_s=cell["slo_s"])
        requests += len(trace)
        lat = rep.latencies()
        level = ServiceLevel(cell["slo_s"])
        rows.append({
            "controller": cell["controller"],
            "reuse": cell["reuse"],
            "slo": cell["slo"],
            "requests": float(len(trace)),
            "completed": float(rep.completed),
            "energy_j": float(rep.total_energy_j),
            "p50_s": lat["p50"], "p95_s": lat["p95"], "p99_s": lat["p99"],
            "violation_rate": rep.slo_violation_rate(),
            "met": float(level.evaluate(rep)["met"]),
        })
    wall_s = time.perf_counter() - t0
    per_req_s = wall_s / max(requests, 1)
    for r in rows:
        emit(f"workloads/http/{r['controller']}/{r['reuse']}/{r['slo']}",
             per_req_s,
             f"p95={r['p95_s']:.2f}s;viol={r['violation_rate']:.3f};"
             f"done={r['completed']:.0f}/{r['requests']:.0f}")
    report = api.Report.from_rows(
        rows, axes=("controller", "reuse", "slo"), derive=False,
        meta={"experiment": "workloads_http", "requests": requests,
              "wall_s": wall_s})
    record = {
        "http_wall_s": wall_s,
        "http_requests_per_sec": requests / wall_s,
        # Mean over the tight-SLO cells: the informational trajectory
        # number (never gated — workload property, not performance).
        "slo_violation_rate": (
            sum(r["violation_rate"] for r in rows if r["slo"] == "tight")
            / max(sum(r["slo"] == "tight" for r in rows), 1)),
    }
    return report, record


# ------------------------------------------------------------ fault churn --

FAULT_DATASETS = (
    (DatasetSpec("bulk-m", 2_500, 24.0 * GB, 2.4),),
    (DatasetSpec("bulk-l", 64, 48.0 * GB, 256.0),),
)


def run_faults(smoke: bool = False) -> dict:
    """Fault-injection leg: asserts conservation, reports churn."""
    n = 12 if smoke else 60
    trace = fleet.poisson_trace(
        rate_per_s=0.05, n_transfers=n, seed=1810,
        datasets=FAULT_DATASETS, controllers=("eemt", "me"),
        profile=CHAMELEON, total_s=3600.0)
    hosts = fleet.host_pool(2, nic_mbps=2.0 * CHAMELEON.bandwidth_mbps,
                            slots=4)
    horizon = max(r.arrival_s for r in trace) + 600.0
    base = FaultSchedule.generate(
        n_hosts=2, horizon_s=horizon, seed=7,
        host_loss_per_hour=18.0, outage_s=60.0,
        nic_degrade_per_hour=12.0, degrade_s=120.0)
    # Kill inside the victim's second wave: admitted at the boundary after
    # arrival, every FAULT_DATASETS transfer runs > 10 s, so a kill at
    # admission + 5 s fires at the next boundary with the lane in flight.
    kills = tuple(
        KillTransfer(trace[i].name,
                     math.ceil(trace[i].arrival_s / 10.0) * 10.0 + 5.0)
        for i in range(0, n, 5))
    out = {}
    for mode in ("resume", "scratch"):
        fs = FaultSchedule(events=base.events + kills, restart=mode)
        c = fleet.run_fleet(trace, hosts, wave_s=10.0, dt=0.5,
                            faults=fs).churn
        if c["goodput_mb"] != c["offered_mb"]:
            raise SystemExit(
                f"workloads/faults[{mode}]: byte conservation broke — "
                f"goodput {c['goodput_mb']!r} != offered "
                f"{c['offered_mb']!r}")
        if mode == "resume" and c["wasted_mb"] != 0.0:
            raise SystemExit(f"workloads/faults[resume]: wasted "
                             f"{c['wasted_mb']} MB, expected bit-exact 0")
        emit(f"workloads/faults/{mode}", 0.0,
             f"kills={c['kills']};restarts={c['restarts']};"
             f"goodput_frac={c['goodput_frac']:.4f};"
             f"wasted={c['wasted_mb']:.0f}MB")
        out[mode] = {k: c[k] for k in
                     ("kills", "host_loss_kills", "transfer_kills",
                      "restarts", "goodput_mb", "wasted_mb",
                      "goodput_frac")}
    return out


# ------------------------------------------------------------ logfit grid --

def synth_log(bin_s: float = 300.0, reps: int = 4) -> tuple:
    """Deterministic synthetic transfer log: a daily-ish sawtooth of path
    bandwidth (fractions of the Chameleon NIC), one saturating transfer
    per bin plus an overlapping half-rate straggler every other bin."""
    bw = CHAMELEON.bandwidth_mbps
    pattern = (1.0, 0.8, 0.45, 0.8)
    records = []
    for k in range(reps * len(pattern)):
        frac = pattern[k % len(pattern)]
        t0 = k * bin_s
        records.append(dict(start_s=t0, end_s=t0 + bin_s,
                            mb=frac * bw * bin_s, rtt_s=CHAMELEON.rtt_s))
        if k % 2:
            records.append(dict(start_s=t0 + 0.25 * bin_s,
                                end_s=t0 + 0.75 * bin_s,
                                mb=0.1 * frac * bw * 0.5 * bin_s))
    return tuple(records)


def logfit_experiment(smoke: bool = False) -> api.Experiment:
    log = synth_log()
    tools = ("EEMT",) if smoke else ("EEMT", "ME", "wget/curl")
    return api.Experiment(
        name="workloads_logfit",
        space=api.grid(
            api.axis("agg", ("sum", "max")),
            api.axis("tool", tools)),
        base={
            "profile": CHAMELEON,
            "datasets": (DatasetSpec("replay", 2_500, 8.0 * GB, 2.4),),
            "controller": lambda c: (api.make_controller(c["tool"])
                                     if c["tool"] in ("EEMT", "ME")
                                     else c["tool"]),
            "environment": lambda c: api.make_environment(
                "logfit", log=log, agg=c["agg"], bin_s=300.0),
            "total_s": 3600.0,
        })


def run_logfit(smoke: bool = False, *, timing: str = "split") -> api.Report:
    report = logfit_experiment(smoke).run(timing=timing)
    secs = report.meta.get("us_per_cell", 0.0) / 1e6
    for row in report.rows():
        emit(f"workloads/logfit/{row['agg']}/{row['tool']}", secs,
             f"{row['avg_tput_gbps']:.3f}Gbps;{row['energy_j']:.0f}J;"
             f"done={int(row['completed'])}")
    return report


# ------------------------------------------------------------------ entry --

def run(smoke: bool = False, warm: bool = False) -> dict:
    """All three legs; ``warm=True`` pre-compiles the HTTP cells' wave
    runners off the clock (the gate metric times steady-state simulation,
    not XLA compile).  The wave width follows each cell's occupancy, so
    the warm-up is one untimed pass of the grid itself."""
    t0 = time.perf_counter()
    if warm:
        run_http(smoke)
    http_report, record = run_http(smoke)
    record["churn"] = run_faults(smoke)
    logfit_report = run_logfit(smoke)
    record.update({
        "wall_s": time.perf_counter() - t0,
        "requests": int(http_report.meta["requests"]),
        "completed": int(sum(http_report["completed"])),
        "smoke": smoke,
        "report": http_report.to_dict(),
        "logfit_report": logfit_report.to_dict(),
    })
    emit("workloads/meta", record["wall_s"],
         f"rps={record['http_requests_per_sec']:.1f};"
         f"viol={record['slo_violation_rate']:.3f};"
         f"kills={record['churn']['resume']['kills']}")
    return record


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grids (80-request cells, 12-transfer "
                         "fault trace, 2-cell logfit)")
    args = ap.parse_args()
    rec = run(smoke=args.smoke)
    print(json.dumps({k: rec[k] for k in
                      ("requests", "completed", "http_requests_per_sec",
                       "slo_violation_rate", "churn", "wall_s")},
                     indent=2))
    if not math.isfinite(rec["http_requests_per_sec"]):
        raise SystemExit("http grid produced no timing")
