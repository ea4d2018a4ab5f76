"""Completion accounting: masking, early exit, and done semantics.

These are the deterministic counterparts of the hypothesis properties in
test_engine_properties.py — no optional dependencies, so they always run.
"""
import jax
import numpy as np
import pytest

from repro import api
from repro.core import CpuProfile, DatasetSpec, engine
from repro.core.types import CHAMELEON, MIXED

CPU = CpuProfile()
ENV = api.as_environment(None).code()       # reference physics

FAST = (DatasetSpec("a", 200, 400.0, 2.0),
        DatasetSpec("b", 10, 600.0, 60.0))


@pytest.mark.parametrize("name", ["eemt", "me", "wget/curl"])
def test_energy_invariant_to_horizon_padding(name):
    """A completed transfer's accounting must not depend on how much padded
    horizon came after it (the substrate freezes at the completion tick)."""
    ctrl = api.make_controller(name, max_ch=64) if name != "wget/curl" \
        else name
    runs = [api.run(api.Scenario(profile=CHAMELEON, datasets=FAST,
                                 controller=ctrl, cpu=CPU, dt=0.25,
                                 total_s=total_s))
            for total_s in (600.0, 1200.0)]
    a, b = runs
    assert a.completed and b.completed
    assert a.time_s == b.time_s
    assert a.energy_j == b.energy_j
    assert a.avg_power_w == b.avg_power_w
    assert a.avg_tput_MBps == b.avg_tput_MBps


def test_completion_time_counts_the_draining_tick():
    """done[i] is recorded post-step: a transfer that drains during tick i
    completed at (i + 1) * dt — finishing on tick 0 takes dt, not 0 s."""
    tiny = (DatasetSpec("tiny", 1, 0.05, 0.05),)
    r = api.run(api.Scenario(profile=CHAMELEON, datasets=tiny,
                             controller="wget/curl", dt=0.5, total_s=60.0))
    assert r.completed
    assert r.time_s >= 0.5                     # never zero / infinite tput
    i = int(np.argmax(r.metrics.done))
    assert r.time_s == pytest.approx(0.5 * (i + 1))
    assert np.isfinite(r.avg_tput_MBps)


@pytest.mark.parametrize("n_steps", [300, 6000])
def test_early_exit_matches_full_horizon_runner(n_steps):
    """Regression: the chunked early-exit runner is bit-identical to the
    reference full-horizon scan — including on transfers that do NOT finish
    inside the horizon (n_steps=300 is too short for the mixed dataset)."""
    ctrl = api.make_controller("eemt", max_ch=64)
    ci = ctrl.init(MIXED, CHAMELEON, CPU)
    inp = jax.tree.map(np.asarray,
                       engine.ScanInputs.from_init(ci, CHAMELEON))
    fast = engine.get_runner(ctrl.code(), ENV, CPU, n_steps, 0.1, 10,
                             batched=False, early_exit=True)
    full = engine.get_runner(ctrl.code(), ENV, CPU, n_steps, 0.1, 10,
                             batched=False, early_exit=False)
    sim_f, ts_f, m_f = jax.tree.map(np.asarray, fast(inp))
    sim_s, ts_s, m_s = jax.tree.map(np.asarray, full(inp))
    completed = bool(np.sum(sim_f.remaining_mb) <= 0.0)
    assert completed == (n_steps == 6000)
    for a, b in zip(jax.tree.leaves((sim_f, ts_f, m_f)),
                    jax.tree.leaves((sim_s, ts_s, m_s))):
        np.testing.assert_array_equal(a, b)


def test_chunking_is_bit_identical():
    """Chunk size is a pure performance knob: any chunking of the horizon
    produces the same results (completion masking freezes padding ticks)."""
    ctrl = api.make_controller("me", max_ch=64)
    ci = ctrl.init(FAST, CHAMELEON, CPU)
    n_steps = 1000
    inp = jax.tree.map(np.asarray,
                       engine.ScanInputs.from_init(ci, CHAMELEON))
    outs = []
    for chunk in (64, 333, 1000):
        runner = engine.get_runner(ctrl.code(), ENV, CPU, n_steps, 0.25, 4,
                                   batched=False, early_exit=True,
                                   chunk=chunk)
        outs.append(jax.tree.map(np.asarray, runner(inp)))
    for other in outs[1:]:
        for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(other)):
            np.testing.assert_array_equal(a, b)


def test_state_freezes_at_completion():
    """SimState.t and energy_j stop at the completion tick; padded horizon
    ticks contribute nothing (the substrate fix, not post-hoc masking)."""
    r = api.run(api.Scenario(profile=CHAMELEON, datasets=MIXED,
                             controller=api.make_controller("eemt",
                                                            max_ch=64),
                             total_s=7200.0))
    assert r.completed
    m = r.metrics
    i = int(np.argmax(m.done))
    # all observables are masked to zero after the draining tick
    assert not m.tput_mbps[i + 1:].any()
    assert not m.power_w[i + 1:].any()
    assert not m.cores[i + 1:].any()
    # energy equals the integral of the masked power trace
    np.testing.assert_allclose(r.energy_j, float(np.sum(m.power_w) * 0.1),
                               rtol=1e-4)
