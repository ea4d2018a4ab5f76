"""``sweep``'s trace-free runners: each lane's completion tick, no traces.

The sweep's runners carry each lane's completion tick through the
early-exit loop instead of writing per-tick traces.  Its results must be
bit for bit what ``api.run`` (which keeps the traces) gives, and the tick
it carries must be where ``api.run``'s ``done`` trace first turns True.
"""
import jax
import numpy as np
import pytest

from repro import api
from repro.api import scenario as _scenario
from repro.core import engine
from repro.core.types import CHAMELEON, CpuProfile, DatasetSpec

CPU = CpuProfile()
SMALL = (DatasetSpec("a", 200, 400.0, 2.0),)
THREE = (DatasetSpec("a", 200, 400.0, 2.0),
         DatasetSpec("b", 10, 600.0, 60.0),
         DatasetSpec("c", 50, 500.0, 10.0))
BIG = (DatasetSpec("big", 100, 200_000.0, 2_000.0),)
THREE_BIG = THREE[:2] + BIG


def scenarios(executor):
    """A 3-lane group of three partitions and a 2-lane group of one, each
    with lanes that complete and a lane that times out, and two singleton
    groups, one of each."""
    def sc(controller, datasets, total_s):
        return api.Scenario(profile=CHAMELEON, datasets=datasets,
                            controller=controller, total_s=total_s,
                            dt=0.25, executor=executor)
    eemt = [api.make_controller("eemt", max_ch=mc) for mc in (4, 8, 16)]
    return [sc(eemt[0], THREE, 40.0), sc(eemt[1], THREE, 40.0),
            sc(eemt[2], THREE_BIG, 40.0),
            sc("me", SMALL, 20.0), sc("me", BIG, 20.0),
            sc("wget/curl", THREE, 30.0), sc("wget/curl", BIG, 10.0)]


def completion_tick(traced):
    """Where ``api.run``'s ``done`` trace first turns True, or -1."""
    done = traced.metrics.done
    return int(np.argmax(done)) if done.any() else -1


@pytest.fixture(scope="module", params=["reference", "blocked"])
def swept(request):
    """One sweep, with the completion tick each lane's result was made
    from, and the output shapes of every runner the sweep called."""
    scs = scenarios(request.param)
    assert api.group_count(scs) == 4
    ticks, shapes = {}, []
    post = _scenario._postprocess
    gets = engine.get_runner, engine.get_sharded_runner

    def spy_post(sim, done_at, prep):
        r = post(sim, done_at, prep)
        ticks[id(r)] = int(done_at)
        return r

    def spy_get(get):
        def wrapped(*args, **kwargs):
            runner = get(*args, **kwargs)

            def call(x):
                shapes.append((args[3], jax.eval_shape(runner, x)))
                return runner(x)
            return call
        return wrapped

    _scenario._postprocess = spy_post
    engine.get_runner, engine.get_sharded_runner = map(spy_get, gets)
    try:
        results = api.sweep(scs)
    finally:
        _scenario._postprocess = post
        engine.get_runner, engine.get_sharded_runner = gets
    return {"scenarios": scs, "results": results,
            "ticks": [ticks[id(r)] for r in results], "shapes": shapes}


def test_sweep_matches_run_bit_for_bit(swept):
    completed = []
    for sc, got, tick in zip(swept["scenarios"], swept["results"],
                             swept["ticks"]):
        want = api.run(sc)
        assert (got.completed, got.time_s, got.energy_j,
                got.avg_tput_MBps) == (want.completed, want.time_s,
                                       want.energy_j, want.avg_tput_MBps)
        assert tick == completion_tick(want)
        assert got.metrics is None and want.metrics is not None
        completed.append(got.completed)
    assert completed == [True, True, False, True, False, True, False]


def test_sweep_runners_emit_no_per_tick_axis(swept):
    """No output leaf of a runner the sweep calls (the batched groups and
    the singletons) has an ``n_steps`` axis; each lane's tick is one
    int32."""
    assert len(swept["shapes"]) == 4
    for n_steps, out in swept["shapes"]:
        sim, _, done_at = out
        assert done_at.dtype == np.int32 and done_at.ndim <= 1
        for leaf in jax.tree.leaves(out):
            assert all(d < n_steps for d in leaf.shape), (n_steps, out)


def test_sharded_runner_emits_no_per_tick_axis():
    """The runner sweep shards groups with is trace-free too (one device
    here stands in for the chips)."""
    scs = scenarios("blocked")[:3]
    preps = [_scenario._prepare(sc) for sc in scs]
    k = preps[0].key
    stacked = jax.tree.map(lambda *xs: np.stack(xs),
                           *[p.inputs for p in preps])
    runner = engine.get_sharded_runner(k.ctrl_code, k.env_code, k.cpu,
                                       k.n_steps, k.dt, k.ctrl_every,
                                       tuple(jax.devices()[:1]),
                                       executor=k.executor)
    out = jax.eval_shape(runner, stacked)
    assert out[2].shape == (3,) and out[2].dtype == np.int32
    for leaf in jax.tree.leaves(out):
        assert all(d < k.n_steps for d in leaf.shape), out


@pytest.mark.parametrize("executor", ["reference", "blocked", "pallas"])
def test_trace_free_core_carries_the_traces_completion_tick(executor):
    """``build_core(traces=False)`` gives the traced core's final state and
    ``argmax`` of its ``done`` trace, early exit or not, and 0 for a lane
    born drained (the traced loop never runs; its buffer reads done)."""
    ctrl = api.make_controller("eemt", max_ch=8)
    env = api.as_environment(None).code()
    n_steps = 200
    inp = jax.tree.map(np.asarray, engine.ScanInputs.from_init(
        ctrl.init(THREE, CHAMELEON, CPU), CHAMELEON))
    drained = inp._replace(total_mb=np.zeros_like(inp.total_mb))
    for x in (inp, drained):
        def core(traces, early_exit=True):
            return engine.build_core(ctrl.code(), env, CPU, n_steps=n_steps,
                                     dt=0.25, ctrl_every=4, chunk=64,
                                     early_exit=early_exit, traces=traces,
                                     executor=executor)
        sim, ts, m = jax.jit(core(True))(x)
        want = int(np.argmax(m.done))
        assert bool(np.asarray(m.done)[-1])
        for early_exit in (True, False):
            got = jax.jit(core(False, early_exit))(x)
            assert int(got[2]) == want, (early_exit, x is drained)
            for a, b in zip(jax.tree.leaves((sim, ts)),
                            jax.tree.leaves(got[:2])):
                np.testing.assert_array_equal(a, b)
    assert want == 0


def test_observe_needs_traces():
    ctrl = api.make_controller("eemt")
    with pytest.raises(ValueError, match="traces"):
        engine.build_core(ctrl.code(), api.as_environment(None).code(), CPU,
                          n_steps=10, dt=0.1, ctrl_every=1, observe=True,
                          traces=False)
