"""Benchmark harness entry point: one module per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run \
        [--only fig2,fig3,fig4,micro,roofline,fleet,learn,dvfs,workloads] \
        [--smoke] [--json BENCH_perf.json]

Prints ``name,us_per_call,derived`` CSV rows (one per benchmark cell) and a
summary of the paper's headline claims at the end.

``--json`` additionally writes a BENCH perf record — the wall-clock metrics
the CI perf-regression gate tracks (see benchmarks/compare.py and the
committed baseline in benchmarks/baselines/) plus the figure/fleet Report
JSON payloads under ``reports`` (the per-cell results the re-baseline loop
and completion-parity check consume).  Compile time is split out into
``*_compile_s`` metrics via the Experiment cold/warm timing split; the
``*_warm_wall_s`` metrics are steady-state (compile-excluded, best-of-3).
``--smoke`` shrinks fig2 and fleet to their CI-sized grids so the record
is comparable across runs of the gate.
"""
from __future__ import annotations

import argparse
import json
import platform
import sys
import time

from .common import use_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only",
                    default="fig2,fig3,fig4,micro,roofline,fleet,"
                            "fleet_online,learn,dvfs,workloads")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized grids for fig2/fleet")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH perf record (wall-clock metrics "
                         "+ Report payloads)")
    args = ap.parse_args()
    only = set(args.only.split(","))
    use_compile_cache()

    print("name,us_per_call,derived")
    summary = {}
    bench = {}
    reports = {}

    if "fig2" in only:
        from . import fig2
        prefix = "fig2_smoke" if args.smoke else "fig2"
        t0 = time.perf_counter()
        report = fig2.run(smoke=args.smoke)
        bench[f"{prefix}_wall_s"] = time.perf_counter() - t0
        if "compile_s" in report.meta:
            bench[f"{prefix}_compile_s"] = report.meta["compile_s"]
        reports[prefix] = report.to_dict()
        if args.json is not None:
            # Warm passes: runners are cached, so these time simulation
            # (not XLA compile) — the stable metric the perf gate compares;
            # best-of-3 because scheduler noise only ever adds time.  The
            # first sample is the split-timing warm pass from above.
            walls = [report.meta["warm_wall_s"]]
            for _ in range(2):
                r = fig2.run(smoke=args.smoke, timing="cold")
                walls.append(r.meta["wall_s"])
            bench[f"{prefix}_warm_wall_s"] = min(walls)
        if not args.smoke:
            summary["fig2_headline"] = fig2.headline(report)

    if "fig3" in only:
        from . import fig3
        r3 = fig3.run()
        if "compile_s" in r3.meta:
            bench["fig3_compile_s"] = r3.meta["compile_s"]
        reports["fig3"] = r3.to_dict()

    if "fig4" in only:
        from . import fig4
        r4 = fig4.run()
        if "compile_s" in r4.meta:
            bench["fig4_compile_s"] = r4.meta["compile_s"]
        reports["fig4"] = r4.to_dict()
        summary["fig4_scaling_contribution"] = fig4.scaling_contribution(r4)

    if "micro" in only:
        from . import micro
        micro.run(bench=bench, smoke=args.smoke)

    if "roofline" in only:
        from . import roofline
        roofline.run()

    if "fleet" in only:
        from . import fleet as fleet_bench
        warm = args.json is not None
        frec = fleet_bench.run(smoke=args.smoke, warm=warm)
        prefix = "fleet_smoke" if args.smoke else "fleet"
        if warm:
            bench[f"{prefix}_warm_wall_s"] = frec["wall_s"]
            bench[f"{prefix}_cold_wall_s"] = frec["cold_wall_s"]
        else:
            bench[f"{prefix}_wall_s"] = frec["wall_s"]
        bench[f"{prefix}_transfers_per_sec"] = frec["transfers_per_sec"]
        reports[prefix] = frec["report"]
        summary["fleet"] = {k: frec[k] for k in
                            ("transfers", "completed", "joules_per_gb",
                             "slowdown")}

    if "fleet_online" in only:
        from . import fleet as fleet_bench
        orec = fleet_bench.run_online(smoke=args.smoke,
                                      warm=args.json is not None)
        # One metric name across smoke/full (the ISSUE-named gate metric);
        # only the smoke record feeds the baseline, so scales never mix.
        bench["fleet_online_wall_s"] = orec["wall_s"]
        bench["fleet_online_transfers_per_sec"] = orec["transfers_per_sec"]
        # Deliberately NOT a _per_sec suffix: peak RSS is informational
        # trajectory data (machine-dependent), never perf-gated and never
        # copied into the baseline by --rebaseline.
        bench["fleet_online_peak_rss_mb"] = orec["peak_rss_mb"]
        if "rss_growth" in orec:
            bench["fleet_online_rss_growth"] = orec["rss_growth"]
            bench["fleet_online_1m_transfers_per_sec"] = \
                orec["transfers_per_sec_1m"]
        prefix = "fleet_online_smoke" if args.smoke else "fleet_online"
        reports[prefix] = orec["report"]
        summary["fleet_online"] = {
            "transfers": orec["transfers"],
            "completed": orec["completed"],
            "joules_per_gb": orec["joules_per_gb"],
            "counters": orec["counters"],
        }

    if "dvfs" in only:
        from . import fig_dvfs
        prefix = "dvfs_smoke" if args.smoke else "dvfs"
        t0 = time.perf_counter()
        rd = fig_dvfs.run(smoke=args.smoke)
        bench[f"{prefix}_wall_s"] = time.perf_counter() - t0
        if "compile_s" in rd.meta:
            bench[f"{prefix}_compile_s"] = rd.meta["compile_s"]
        reports[prefix] = rd.to_dict()
        if args.json is not None:
            walls = [rd.meta["warm_wall_s"]]
            for _ in range(2):
                r = fig_dvfs.run(smoke=args.smoke, timing="cold")
                walls.append(r.meta["wall_s"])
            bench[f"{prefix}_warm_wall_s"] = min(walls)
            bench[f"{prefix}_cells_per_sec"] = len(rd) / min(walls)
        if not args.smoke:
            summary["dvfs_headline"] = fig_dvfs.headline(rd)

    if "workloads" in only:
        from . import workloads as workloads_bench
        wrec = workloads_bench.run(smoke=args.smoke,
                                   warm=args.json is not None)
        prefix = "workloads_smoke" if args.smoke else "workloads"
        bench[f"{prefix}_wall_s"] = wrec["wall_s"]
        # One gate-metric name across smoke/full (only the smoke record
        # feeds the baseline, so scales never mix).
        bench["http_requests_per_sec"] = wrec["http_requests_per_sec"]
        # Deliberately NOT a _per_sec suffix: the SLO-violation rate is a
        # workload property (informational trajectory data), never
        # perf-gated and never copied into the baseline by --rebaseline.
        bench["workloads_slo_violation_rate"] = wrec["slo_violation_rate"]
        reports[prefix] = wrec["report"]
        reports[f"{prefix}_logfit"] = wrec["logfit_report"]
        summary["workloads"] = {
            "requests": wrec["requests"],
            "completed": wrec["completed"],
            "slo_violation_rate": wrec["slo_violation_rate"],
            "churn": wrec["churn"],
        }

    if "learn" in only:
        from . import learn as learn_bench
        prefix = "learn_smoke" if args.smoke else "learn"
        t0 = time.perf_counter()
        lrec = learn_bench.run(smoke=args.smoke,
                               warm=args.json is not None)
        bench[f"{prefix}_wall_s"] = time.perf_counter() - t0
        bench[f"{prefix}_train_s"] = lrec["train_s"]
        if "compile_s" in lrec:
            bench[f"{prefix}_compile_s"] = lrec["compile_s"]
        if args.json is not None:
            bench[f"{prefix}_eval_warm_wall_s"] = lrec["eval_warm_wall_s"]
            bench[f"{prefix}_eval_cells_per_sec"] = \
                lrec["eval_cells_per_sec"]
        reports[prefix] = lrec["report"]
        reports[f"{prefix}_fleet"] = lrec["fleet_report"]
        summary["learn"] = {"teacher": lrec["teacher"],
                            "samples": lrec["samples"],
                            "loss_last": lrec["loss_last"],
                            "vs_teacher": lrec["vs_teacher"]}

    if args.json is not None:
        record = {
            "metrics": bench,
            "reports": reports,
            "meta": {
                "python": platform.python_version(),
                "machine": platform.machine(),
                "smoke": args.smoke,
            },
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}", file=sys.stderr)

    if summary:
        print("# summary", json.dumps(summary, indent=2), file=sys.stderr)


if __name__ == "__main__":
    main()
