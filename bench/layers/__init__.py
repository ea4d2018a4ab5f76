"""Per-layer metric readers, one file per metric named as in
``BENCHMARK.json``: ``<metric>.py`` defines ``read(ctx)``, which returns the
metric's value or ``None`` when there is nothing to read.

``ctx`` holds ``trace`` (:func:`bench.trace_reduce.reduce` of the traced
window), ``counters`` (what the workload counted over it: ``units`` of
``unit`` (grid passes) and ``tick_bytes``, plus its own extras) and
``peaks`` (the device's row of ``peaks.json``).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

from bench import rooflines

HERE = Path(__file__).resolve().parent


def load(metric: str):
    """The ``read`` function of one metric's reader file."""
    path = HERE / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_layer_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def busy_s(ctx) -> float | None:
    """Device busy seconds in the window, averaged over the chips."""
    busy = ctx["trace"]["busy_s"]
    return sum(busy) / len(busy) if busy and sum(busy) > 0 else None


def per_unit_ms(ctx) -> float | None:
    busy, units = busy_s(ctx), ctx["counters"]["units"]
    return busy * 1e3 / units if busy and units else None


def idle_pct(ctx) -> float | None:
    busy = busy_s(ctx)
    return (100.0 * (1.0 - busy / ctx["trace"]["window_s"])
            if busy else None)


def tick_roofline_pct(ctx) -> float | None:
    """Share of the HBM roofline: the least time the tick bytes need at
    the chips' peak bandwidth over the time the chips were busy."""
    busy = sum(ctx["trace"]["busy_s"])
    n_bytes = ctx["counters"]["tick_bytes"]
    if not busy or not n_bytes:
        return None
    return rooflines.roofline_pct(n_bytes, busy, ctx["peaks"])
