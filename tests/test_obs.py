"""repro.obs: host spans inside ``api.sweep`` and the program counter.

Spans are kept only while a profiler trace is active; they nest as
sweep > prepare | group > launch | wait | fetch | postprocess, share one
trace id per sweep, and appear on the profiler's host plane.  The compile
counter is always on and puts each program under the innermost open span.
"""
import glob
import os
from collections import Counter

import jax
import numpy as np
import pytest

from repro import api, obs
from repro.api import scenario as _scenario
from repro.core import engine
from repro.core.types import CHAMELEON, DatasetSpec

FAST = (DatasetSpec("a", 200, 400.0, 2.0),
        DatasetSpec("b", 10, 600.0, 60.0))
ONE = (DatasetSpec("a", 200, 400.0, 2.0),)
LEAVES = {"sweep.prepare", "sweep.launch", "sweep.wait", "sweep.fetch",
          "sweep.postprocess"}


def scenarios(total_s=60.0):
    """Two groups (3 and 2 lanes) and one singleton."""
    out = [api.Scenario(profile=CHAMELEON, datasets=FAST,
                        controller=api.make_controller("eemt", max_ch=mc),
                        total_s=total_s, dt=0.25) for mc in (4, 8, 16)]
    out += [api.Scenario(profile=CHAMELEON, datasets=ONE, controller="me",
                         total_s=total_s / 2, dt=0.25) for _ in range(2)]
    out.append(api.Scenario(profile=CHAMELEON, datasets=FAST,
                            controller="wget/curl", total_s=total_s * 2 / 3,
                            dt=0.25))
    return out


def same_bits(a, b) -> bool:
    return (a.completed == b.completed and a.time_s == b.time_s
            and a.energy_j == b.energy_j
            and a.avg_tput_MBps == b.avg_tput_MBps
            and all((x == y).all() for x, y in zip(
                jax.tree.leaves(a.metrics), jax.tree.leaves(b.metrics))))


@pytest.fixture(scope="module")
def swept(tmp_path_factory):
    """One sweep with the profiler off, then the same sweep traced, with
    what each of its ``_fetch`` calls returned."""
    scs = scenarios()
    assert api.group_count(scs) == 3
    obs.clear()
    off = api.sweep(scs)
    kept_off = obs.spans()
    log_dir = str(tmp_path_factory.mktemp("trace"))
    fetch, fetched = _scenario._fetch, []

    def spy_fetch(*args):
        fetched.append(fetch(*args))
        return fetched[-1]

    _scenario._fetch = spy_fetch
    try:
        with jax.profiler.trace(log_dir):
            on = api.sweep(scs)
    finally:
        _scenario._fetch = fetch
    recs = obs.spans()
    obs.clear()
    return {"off": off, "on": on, "kept_off": kept_off, "records": recs,
            "log_dir": log_dir, "fetched": fetched}


def test_no_record_without_profiler(swept):
    assert swept["kept_off"] == []


def test_answers_bit_identical_with_profiler_on_and_off(swept):
    assert all(same_bits(a, b) for a, b in zip(swept["off"], swept["on"]))


def test_spans_nest_under_one_trace(swept):
    recs = swept["records"]
    by_id = {r.span_id: r for r in recs}
    roots = [r for r in recs if r.parent_id is None]
    assert [r.name for r in roots] == ["sweep"]
    root = roots[0]
    assert root.meta == {"scenarios": 6, "groups": 3}
    assert {r.trace_id for r in recs} == {root.span_id}
    groups = [r for r in recs if r.name == "sweep.group"]
    assert sorted(g.meta["lanes"] for g in groups) == [1, 2, 3]
    for r in recs:
        if r.name in ("sweep.prepare", "sweep.group"):
            assert r.parent_id == root.span_id
        elif r.name != "sweep":
            assert r.name in LEAVES
            assert by_id[r.parent_id].name == "sweep.group"
        if r.parent_id is not None:
            parent = by_id[r.parent_id]
            assert parent.start_ns <= r.start_ns <= r.end_ns <= parent.end_ns
    # Every group launches, waits, fetches and postprocesses.
    for g in groups:
        names = {r.name for r in recs if r.parent_id == g.span_id}
        assert names == LEAVES - {"sweep.prepare"}


def test_leaves_do_not_overlap(swept):
    leaves = sorted((r.start_ns, r.end_ns) for r in swept["records"]
                    if r.name in LEAVES)
    assert len(leaves) == 1 + 3 * 5       # prepare; 2 groups; singleton
    for (_, end), (start, _) in zip(leaves, leaves[1:]):
        assert end <= start


def test_fetch_bytes_are_the_results_metrics(swept):
    """``sweep.fetch``'s bytes are what ``_fetch`` copies: each lane's
    final state and completion tick, and no per-tick metrics."""
    fetched = [r.meta["bytes"] for r in swept["records"]
               if r.name == "sweep.fetch"]
    copied = [sum(leaf.nbytes for leaf in jax.tree.leaves(out))
              for out in swept["fetched"]]
    assert fetched == copied
    assert all(r.metrics is None for r in swept["on"])
    for (_, done_at), lanes in zip(swept["fetched"], (3, 2, 1)):
        assert done_at.dtype == np.int32 and done_at.size == lanes


def test_launch_bytes_are_the_stacked_inputs(swept):
    """``sweep.launch``'s bytes are what a group hands its runner: each
    lane's scalars and partition rows, lanes padded to two at least.
    They follow lanes and partitions, not the horizon: no scenario here
    has a ``bw_schedule``, so none carries an [n_steps] row."""
    recs = swept["records"]
    lanes = {r.span_id: r.meta["lanes"] for r in recs
             if r.name == "sweep.group"}
    launched = sorted((lanes[r.parent_id], r.meta["bytes"]) for r in recs
                      if r.name == "sweep.launch" and "bytes" in r.meta)
    assert [n for n, _ in launched] == [1, 2, 3]

    def lane_bytes(sc):
        return sum(leaf.nbytes for leaf in
                   jax.tree.leaves(_scenario._prepare(sc).inputs))

    scs = scenarios()
    wsc, me, eemt = scs[5], scs[3], scs[0]
    assert launched == [(n, max(n, 2) * lane_bytes(sc))
                        for n, sc in ((1, wsc), (2, me), (3, eemt))]
    longer = scenarios(total_s=600.0)
    assert [lane_bytes(sc) for sc in longer] == \
        [lane_bytes(sc) for sc in scs]


def test_spans_on_the_profilers_host_plane(swept):
    path, = glob.glob(os.path.join(swept["log_dir"], "plugins", "profile",
                                   "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host = [e.name for plane in data.planes if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events
            if e.name.startswith("sweep")]
    assert Counter(host) == Counter(r.name for r in swept["records"])


def test_cap_drops_and_counts(monkeypatch, tmp_path):
    monkeypatch.setattr(obs, "CAP", 3)
    obs.clear()
    with jax.profiler.trace(str(tmp_path)):
        for i in range(5):
            with obs.span("probe", i=i):
                pass
    assert [r.meta["i"] for r in obs.spans()] == [0, 1, 2]
    assert obs.dropped() == 2
    obs.clear()
    assert obs.spans() == [] and obs.dropped() == 0


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] != before.get(k, 0)}


def test_compiles_attributed_to_the_span_that_asked():
    """A cold sweep compiles only the engine runners, one per group, in
    ``sweep.launch``: controller ``init`` runs on the host, so
    ``sweep.prepare`` compiles nothing.  The same sweep again compiles
    nothing."""
    jax.clear_caches()
    engine.clear_runner_caches()
    scs = scenarios(total_s=45.0)      # a horizon no other test compiles
    c0 = obs.counters()
    api.sweep(scs)
    c1 = obs.counters()
    programs = delta(c0["programs"], c1["programs"])
    assert programs == {"sweep.launch": api.group_count(scs)}, programs
    assert set(delta(c0["compile_s"], c1["compile_s"])) <= set(programs)
    api.sweep(scenarios(total_s=45.0))
    assert obs.counters()["programs"] == c1["programs"]

