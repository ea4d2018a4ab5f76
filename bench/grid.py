"""Grid cells: whole scenario grids through ``Experiment.run`` -> ``sweep``.

The configuration (``kind: grid``) names testbeds, datasets and tools; the
grid is their product, each cell simulated to completion or to its
testbed's horizon at ``dt``.  The traffic mix names its generator, whose
``plan`` gives the cells of one pass in order, each with the scenario
fields it overrides; the plan is drawn once in set-up.  The window then
runs whole grid passes back to back.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

from repro import api

from . import build, generators, reference, rooflines
from .checks import Check, gap_checks


OVERRIDES = ("bw_schedule",)        # the scenario fields the reference follows


class GridWorkload:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices: tuple):
        self.cfg, self.devices = cfg, devices
        dt, tuner = cfg["dt"], cfg["tuner"]
        horizon = cfg["horizon_s"]
        self.exp = api.Experiment(
            name=cfg["name"],
            space=api.grid(
                api.axis("testbed", {k: build.profile(k, v) for k, v in
                                     cfg["testbeds"].items()},
                         field="profile"),
                api.axis("dataset", {k: build.datasets(v) for k, v in
                                     cfg["datasets"].items()},
                         field="datasets"),
                api.axis("tool", list(cfg["tools"]))),
            base={"cpu": build.cpu(cfg["cpu"]), "dt": dt,
                  "controller": lambda c: build.controller(c["tool"], tuner),
                  "total_s": lambda c: horizon[c["profile"].name]})
        grid = self.exp.cells()
        plan = generators.load(mix["generator"]).plan(seed, mix, len(grid))
        self.cells, self.spec = [], []
        for k, over in plan:
            if set(over) - set(OVERRIDES):
                raise ValueError(f"the reference follows only {OVERRIDES}, "
                                 f"not {sorted(set(over) - set(OVERRIDES))}")
            cell = grid[k]
            self.cells.append(dataclasses.replace(
                cell, scenario=dataclasses.replace(cell.scenario, **over)))
            labels = cell.labels
            steps = int(round(horizon[labels["testbed"]] / dt))
            self.spec.append({
                "tool": labels["tool"],
                "datasets": cfg["datasets"][labels["dataset"]],
                "path": cfg["testbeds"][labels["testbed"]],
                "horizon_s": horizon[labels["testbed"]],
                "bw": over.get("bw_schedule", np.ones(steps, np.float32))})
        self.passes: list = []       # every pass's rows, for the check
        self.results: list = []      # the last sweep's TransferResults

    def _sweep(self, scenarios):
        with jax.profiler.TraceAnnotation("api.sweep"):
            self.results = api.sweep(scenarios, devices=self.devices)
        return self.results

    def run_pass(self) -> None:
        report = self.exp.run(cells=self.cells, sweeper=self._sweep)
        self.passes.append([(r["completed"], r["time_s"], r["energy_j"],
                             r["avg_tput_MBps"] * r["time_s"])
                            for r in report.rows()])

    def warm(self) -> None:
        self.run_pass()
        self.passes.clear()

    def measure(self, seconds: float) -> dict:
        """Whole passes until ``seconds`` have passed; the rate is over the
        wall time of all of them."""
        t0 = time.perf_counter()
        while True:
            self.run_pass()
            wall = time.perf_counter() - t0
            if wall >= seconds:
                break
        return {"cells_per_s": len(self.cells) * len(self.passes) / wall}

    def traced(self, seconds: float, tracer) -> dict:
        """One pass under the profiler; what the layer readers need."""
        with tracer:
            self.run_pass()
        return {"units": 1, "unit": "pass",
                "tick_bytes": rooflines.grid_tick_bytes(
                    [t for _, t, _, _ in self.passes[-1]],
                    [len(s["datasets"]) for s in self.spec],
                    self.cfg["dt"]),
                "trace_copy_bytes": sum(
                    leaf.nbytes for r in self.results
                    for leaf in jax.tree.leaves(r.metrics))}

    def attempted(self) -> tuple[int, int]:
        rows = [row for p in self.passes for row in p]
        return len(rows), sum(1 for row in rows if not row[0])

    def answers(self) -> list:
        return [{"completed": bool(c), "time_s": t, "energy_j": e,
                 "moved_mb": m} for p in self.passes for (c, t, e, m) in p]

    def check(self) -> list[Check]:
        """Every pass of the window against the reference."""
        return self.compare(self.answers())

    def reference_answers(self, dtype, device=None) -> list:
        return reference.grid(self.spec, self.cfg, dtype,
                              device)

    def compare(self, got: list) -> list[Check]:
        """``got``: whole passes of answers, in cell order."""
        want = reference.grid(self.spec, self.cfg)
        return gap_checks(got, want * (len(got) // len(want)),
                          self.cfg["dt"], self.cfg["limits"])

Workload = GridWorkload
