"""The bandwidth input's two ranks: a scenario without a ``bw_schedule``
carries one float32 share per lane (``ScanInputs.bw`` of rank 0), one
with a schedule its ``[n_steps]`` row.

The rank is what the engine sees (``engine.tick_bw``), so the two kinds
never stack into one array: they group apart and compile apart.  A lane
without a schedule reads bit for bit what the same lane reads under a
schedule of ones, on every executor, in ``run``, ``sweep`` and the
learned-controller rollouts, and prepares no ``[n_steps]`` array.
"""
import os
import sys

import jax
import numpy as np
import pytest

from repro import api, learn, obs
from repro.api import scenario as _scenario
from repro.core import engine
from repro.core.types import CHAMELEON, CLOUDLAB, DatasetSpec

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from benchmarks import fig2  # noqa: E402

FAST = (DatasetSpec("a", 200, 400.0, 2.0),
        DatasetSpec("b", 10, 600.0, 60.0))
ONE = (DatasetSpec("c", 50, 500.0, 10.0),)
TOTAL_S, DT = 60.0, 0.1
N_STEPS = int(round(TOTAL_S / DT))


def _fig2_like(executor="auto", schedule=None):
    """Fig. 2-style cells: two testbeds, a tuner and two static tools,
    one and two datasets."""
    cells = [(CHAMELEON, "eemt", FAST), (CHAMELEON, "me", FAST),
             (CLOUDLAB, "wget/curl", ONE), (CLOUDLAB, "eemt", ONE)]
    return [api.Scenario(profile=p, datasets=ds,
                         controller=api.make_controller(c), total_s=TOTAL_S,
                         dt=DT, executor=executor,
                         bw_schedule=None if schedule is None
                         else schedule(i))
            for i, (p, c, ds) in enumerate(cells)]


def _ones(_):
    return np.ones(N_STEPS, np.float32)


def _slowed(i):
    """A schedule that moves the answers: the path at 40-70 % of its rate."""
    return np.full(N_STEPS, 0.4 + 0.1 * i, np.float32)


def _same(a, b) -> bool:
    return (a.name, a.completed, a.time_s, a.energy_j, a.avg_tput_MBps,
            a.avg_power_w) == (b.name, b.completed, b.time_s, b.energy_j,
                               b.avg_tput_MBps, b.avg_power_w)


def _same_traces(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in
               zip(jax.tree.leaves(a.metrics), jax.tree.leaves(b.metrics)))


@pytest.mark.parametrize("executor", ["reference", "blocked", "pallas"])
def test_unscheduled_is_bit_identical_to_a_schedule_of_ones(executor):
    free = _fig2_like(executor)
    ones = _fig2_like(executor, _ones)
    for a, b in zip(api.sweep(free), api.sweep(ones)):
        assert a.completed and _same(a, b), (executor, a, b)
    for sa, sb in zip(free, ones):
        a, b = api.run(sa), api.run(sb)
        assert _same(a, b) and _same_traces(a, b), (executor, sa.name)


def test_prepared_inputs_hold_no_horizon_row():
    """Every leaf ``sweep.prepare`` builds for the Figure 2 grid is
    smaller than the horizon; a given schedule is the one row."""
    for cell in fig2.experiment(smoke=False).cells():
        prep = _scenario._prepare(cell.scenario)
        assert not prep.key.scheduled
        assert np.shape(prep.inputs.bw) == ()
        assert prep.inputs.bw.dtype == np.float32
        for leaf in jax.tree.leaves(prep.inputs):
            assert np.size(leaf) < prep.key.n_steps
    sc = _fig2_like(schedule=_slowed)[0]
    prep = _scenario._prepare(sc)
    assert prep.key.scheduled
    np.testing.assert_array_equal(prep.inputs.bw, sc.bw_schedule)


def test_schedule_shape_is_still_checked():
    sc = _fig2_like(schedule=lambda _: np.ones(N_STEPS - 1, np.float32))[0]
    with pytest.raises(ValueError, match="bw_schedule shape"):
        _scenario._prepare(sc)


def test_mixed_sweep_matches_each_kind_alone(monkeypatch):
    """Scheduled and unscheduled scenarios interleaved: results in input
    order, each equal to its own kind's sweep; one runner launch per
    group of ``group_count``, and one executable per kind."""
    free = _fig2_like()
    slowed = _fig2_like(schedule=_slowed)
    mixed = [sc for pair in zip(free, slowed) for sc in pair]
    want = [r for pair in zip(api.sweep(free), api.sweep(slowed))
            for r in pair]
    assert any(a.time_s != b.time_s for a, b in zip(want[::2], want[1::2]))

    launches = []
    run_group = _scenario._run_group

    def counted(key, stacked, batch, devices):
        launches.append((key, np.ndim(stacked.bw)))
        return run_group(key, stacked, batch, devices)

    monkeypatch.setattr(_scenario, "_run_group", counted)
    engine.clear_runner_caches()
    before = obs.counters()["programs"].get("sweep.launch", 0)
    got = api.sweep(mixed)
    compiled = obs.counters()["programs"].get("sweep.launch", 0) - before

    assert [(r.name, r.time_s) for r in got] == \
        [(r.name, r.time_s) for r in want]
    assert all(_same(a, b) for a, b in zip(got, want))
    assert len(launches) == api.group_count(mixed) == compiled
    # Each kind stacks alone: [B] shares or [B, n_steps] rows, never mixed.
    assert {rank for key, rank in launches if key.scheduled} == {2}
    assert {rank for key, rank in launches if not key.scheduled} == {1}
    kinds = {key._replace(scheduled=False) for key, _ in launches}
    assert len(launches) == 2 * len(kinds)


def test_learn_rollouts_read_the_scalar_share():
    """``repro.learn``'s policy rollout (its own scan) and the observed
    engine runs take the rank-0 share and match a schedule of ones."""
    cfg = learn.PolicyConfig()
    params = learn.init_policy(cfg, jax.random.PRNGKey(0))
    ctrl = learn.LearnedController(
        params=jax.tree.map(np.asarray, params), cfg=cfg)
    outs = []
    for schedule in (None, _ones):
        scs = [api.Scenario(profile=p, datasets=FAST, controller=ctrl,
                            total_s=TOTAL_S, dt=DT,
                            bw_schedule=None if schedule is None
                            else schedule(0))
               for p in (CHAMELEON, CLOUDLAB)]
        preps = [_scenario._prepare(sc) for sc in scs]
        key = preps[0].key
        stacked = jax.tree.map(lambda *xs: np.stack(xs),
                               *[p.inputs for p in preps])
        assert np.ndim(stacked.bw) == (1 if schedule is None else 2)
        rollout = jax.jit(learn.make_policy_rollout(
            cfg, key.env_code, key.cpu, n_steps=key.n_steps, dt=key.dt,
            ctrl_every=key.ctrl_every))
        noise = np.zeros((2, learn.n_ctrl_ticks(key.n_steps, key.ctrl_every),
                          cfg.n_heads, cfg.n_classes), np.float32)
        observed = learn.run_observed(scs)
        outs.append((rollout(params, noise, stacked),
                     [(o.sim, o.metrics, o.obs) for o in observed]))
    (roll_a, obs_a), (roll_b, obs_b) = outs
    for x, y in zip(jax.tree.leaves((roll_a, obs_a)),
                    jax.tree.leaves((roll_b, obs_b))):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("early_exit", [True, False])
def test_a_scalar_share_reads_as_a_constant_row(early_exit):
    """A rank-0 share below 1 reaches every tick as a runtime input: the
    engine core gives what the same share as an ``[n_steps]`` row gives,
    per-tick traces included, and not what the full rate gives."""
    prep = _scenario._prepare(_fig2_like()[0])
    k = prep.key
    runner = engine.get_runner(k.ctrl_code, k.env_code, k.cpu, k.n_steps,
                               k.dt, k.ctrl_every, batched=False,
                               early_exit=early_exit, chunk=64)
    share = np.float32(0.55)
    scalar = runner(prep.inputs._replace(bw=share))
    row = runner(prep.inputs._replace(bw=np.full(k.n_steps, share)))
    full = runner(prep.inputs)
    for x, y in zip(jax.tree.leaves(scalar), jax.tree.leaves(row)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert float(scalar[0].t) != float(full[0].t)
