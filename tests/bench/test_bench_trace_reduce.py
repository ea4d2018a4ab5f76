"""The reduction from a profiler trace to busy time, program executions,
top ops and idle gaps: by hand on a made-up trace, and on a small trace
recorded on a TPU v5e chip (a two-lane sweep of 5 s transfers), trimmed."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"
MADE_UP = {
    "devices": {DEV: {
        # A loop (10-40) enclosing two ops, an overlapping op (35-50),
        # and one op after a gap (70-80).
        "ops": [["%while", 10, 40], ["%fusion.1", 12, 20],
                ["%fusion.2", 25, 30], ["%copy", 35, 50],
                ["%fusion.1", 70, 80]],
        "modules": [["jit_core", 10, 50], ["jit_ceil", 70, 80]]}},
    "host": [["bench.window", 0, 100, "python"],
             ["api.sweep", 5, 95, "python"],
             ["np.asarray(jax.Array)", 52, 68, "python"]],
}


def test_made_up_trace_by_hand():
    red = tr.reduce(MADE_UP)
    assert red["window_s"] == pytest.approx(100e-9)
    # Union: 10-50 and 70-80.
    assert red["busy_s"] == [pytest.approx(50e-9)]
    assert red["executions"] == [2]
    ops = dict(red["device_ops"])
    # Self times: the loop 30 - 8 - 5 - 5 (the copy overlaps its last
    # 5) = 12; fusion.1 8 + 10; fusion.2 5; copy 15.  They add up to the
    # busy time.
    assert ops["%while"] == pytest.approx(12e-9)
    assert ops["%fusion.1"] == pytest.approx(18e-9)
    assert ops["%copy"] == pytest.approx(15e-9)
    assert sum(ops.values()) == pytest.approx(50e-9)
    gaps = red["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx([20e-9, 20e-9, 10e-9])
    # 50-70 is labelled by what the host did in its middle (60).
    assert "api.sweep/np.asarray(jax.Array)" in [g[0] for g in gaps]


def test_window_clips():
    lo, hi = 15, 45
    dev = MADE_UP["devices"][DEV]
    assert tr.busy_ns(dev, lo, hi) == 30
    assert tr.executions(dev, lo, hi) == 0
    assert tr.executions(dev, 0, 100) == 2


RECORDED = Path(__file__).parent / "data" / "sweep_trace.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_trace():
    trace = json.loads(RECORDED.read_text())
    red = tr.reduce(trace)
    lo, hi = tr.window(trace)
    dev = trace["devices"][DEV]
    # Busy time against a plain per-nanosecond count of covered points.
    covered = np.zeros(int(hi - lo) + 1, bool)
    for _, s, e in dev["ops"]:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            covered[int(s - lo):int(e - lo)] = True
    assert red["busy_s"][0] == pytest.approx(covered.sum() / 1e9, rel=1e-3)
    assert 0 < red["busy_s"][0] < red["window_s"]
    gaps = tr.idle_gaps(dev, trace["host"], lo, hi, k=10 ** 9)
    assert sum(g[1] for g in gaps) + red["busy_s"][0] == \
        pytest.approx(red["window_s"], rel=1e-6)
    assert red["executions"][0] == sum(
        1 for _, s, _ in dev["modules"] if lo <= s < hi) > 0
    assert sum(t for _, t in tr.top_ops([dev], lo, hi, k=10 ** 9)) == \
        pytest.approx(red["busy_s"][0], rel=1e-6)
