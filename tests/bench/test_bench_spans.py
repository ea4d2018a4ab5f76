"""The span readers (``bench/spans.py``): per-unit milliseconds of each of
the sweep's leaf spans, what the leaves leave of the traced window, and
``None`` wherever the program keeps no record.  Hand-made records first,
then one small grid pass traced on the CPU through the harness's own
tracer and read by the seven reader files."""
from __future__ import annotations

import copy
import sys
from collections import namedtuple
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import layers as readers  # noqa: E402
from bench import spans  # noqa: E402

Rec = namedtuple("Rec", "name span_id parent_id trace_id start_ns end_ns "
                        "meta")
MS = 1_000_000
READERS = ("prepare_ms.grid", "launch_ms.grid", "wait_ms.grid",
           "fetch_ms.grid", "postprocess_ms.grid", "untraced_ms.grid",
           "programs.grid")


def pass_records(t0: int = 0) -> list:
    """One sweep of two groups: prepare 5 ms; per group launch 2, wait 10,
    fetch 4, postprocess 1 ms; 1 ms of the root between leaves."""
    recs = [Rec("sweep.prepare", 2, 1, 1, t0, t0 + 5 * MS, {})]
    t = t0 + 5 * MS
    for g in (3, 4):
        start = t
        for name, ms in (("sweep.launch", 2), ("sweep.wait", 10),
                         ("sweep.fetch", 4), ("sweep.postprocess", 1)):
            recs.append(Rec(name, 10 * g, g, 1, t, t + ms * MS, {}))
            t += ms * MS
        recs.append(Rec("sweep.group", g, 1, 1, start, t, {}))
    recs.append(Rec("sweep", 1, None, 1, t0, t + MS, {}))
    return recs


@pytest.mark.parametrize("units", [1, 2])
@pytest.mark.parametrize("name,ms", [("sweep.prepare", 5),
                                     ("sweep.launch", 4),
                                     ("sweep.wait", 20),
                                     ("sweep.fetch", 8),
                                     ("sweep.postprocess", 2)])
def test_leaf_ms_per_unit(name, ms, units):
    recs = pass_records() + (pass_records(10**9) if units == 2 else [])
    assert spans.leaf_ms(recs, name, units) == pytest.approx(ms)


def test_untraced_ms_is_the_window_less_the_leaves():
    recs = pass_records()
    # Leaves: 5 + 2 * (2 + 10 + 4 + 1) = 39 ms of a 50 ms window.
    assert spans.untraced_ms(recs, 0.050, 1) == pytest.approx(11.0)
    assert spans.untraced_ms(recs + pass_records(10**9), 0.100, 2) == \
        pytest.approx(11.0)


def test_nothing_to_read_is_none():
    assert spans.leaf_ms([], "sweep.fetch", 1) is None
    assert spans.leaf_ms(None, "sweep.fetch", 1) is None
    assert spans.untraced_ms(None, 1.0, 1) is None
    assert spans.leaf_ms(pass_records(), "sweep.nothing", 1) is None
    assert spans.leaf_ms(pass_records(), "sweep.fetch", 0) is None


def test_program_without_obs_reads_none(monkeypatch):
    """A program that keeps no span or counter (one without
    ``repro.obs``): every reader gives ``None`` and none raises."""
    sys.path.insert(0, str(ROOT / "src"))
    import repro
    monkeypatch.delattr(repro, "obs", raising=False)
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    ctx = {"trace": {"window_s": 1.0}, "counters": {"units": 1},
           "peaks": None}
    assert spans.records() is None and spans.programs() is None
    for name in READERS:
        assert readers.load(name)(ctx) is None, name


def test_traced_grid_pass_reads_every_metric(tmp_path):
    """A small grid pass under the harness's tracer, on the CPU: each
    reader gives a number, the five leaves and ``untraced_ms`` add up to
    the window, and the program counter counts what the harness's compile
    clock counts (compiles and cache loads), warm-up included."""
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from bench import grid, run, trace_reduce
    from repro import obs

    _, cfg, mix, _ = run.load_cell("fig2.grid")
    cfg = copy.deepcopy(cfg)
    cfg["testbeds"] = {"chameleon": cfg["testbeds"]["chameleon"]}
    cfg["horizon_s"] = {"chameleon": 600.0}
    cfg["datasets"] = {k: cfg["datasets"][k] for k in ("small", "mixed")}
    cfg["tools"] = ["wget/curl", "ME", "EEMT"]
    before = spans.programs()
    clock = run.CompileClock(jax)
    w = grid.GridWorkload(cfg, mix, 2 ** 31 + 5, tuple(jax.devices()[:1]))
    w.warm()
    obs.clear()
    tracer = run.Tracer(jax, tmp_path / "trace")
    counters = w.traced(1.0, tracer)
    reduced = trace_reduce.reduce(trace_reduce.from_xplane(
        trace_reduce.find_xplane(str(tracer.log_dir))))
    ctx = {"trace": reduced, "counters": counters, "peaks": None}
    got = {name: readers.load(name)(ctx) for name in READERS}
    obs.clear()
    assert all(v is not None for v in got.values()), got
    assert all(v >= 0 for v in got.values()), got
    window_ms = reduced["window_s"] * 1e3
    assert sum(got[n] for n in READERS[:6]) == pytest.approx(window_ms)
    assert got["untraced_ms.grid"] < window_ms
    assert got["programs.grid"] - before == clock.requests > 0
