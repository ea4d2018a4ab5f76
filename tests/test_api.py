"""Tests for the repro.api surface: registry, Scenario run/sweep batching,
and the removal tombstones of the pre-PR 2 legacy surface."""
import numpy as np
import pytest

from repro import api
from repro.core import CpuProfile
from repro.core.baselines import BASELINE_BUILDERS
from repro.core.types import CHAMELEON, CLOUDLAB, DatasetSpec

CPU = CpuProfile()

# Small synthetic partitions so one run is ~1-2k scan steps.
FAST = (DatasetSpec("a", 200, 400.0, 2.0),
        DatasetSpec("b", 10, 600.0, 60.0))
TOTAL_S = 120.0


def _mk(name):
    if name in ("eett", "ismail-target"):
        return api.make_controller(name, target_tput_mbps=400.0)
    return api.make_controller(name)


# ------------------------------------------------------------- registry ---

def test_registry_roundtrips_every_name():
    names = api.list_controllers()
    assert set(BASELINE_BUILDERS) <= set(names)
    assert {"me", "eemt", "eett", "ismail-target"} <= set(names)
    for name in names:
        ctrl = _mk(name)
        assert isinstance(ctrl, api.Controller)
        # as_controller is idempotent on protocol instances
        assert api.as_controller(ctrl) is ctrl
        # code() is hashable + stable (the vmap group key)
        assert hash(ctrl.code()) == hash(ctrl.code())


def test_make_controller_case_insensitive_and_kwargs():
    a = api.make_controller("EEMT", max_ch=32)
    b = api.make_controller("eemt", max_ch=32)
    assert a == b
    assert a.sla.max_ch == 32
    assert a.code() == b.code()


def test_unknown_controller_raises():
    with pytest.raises(KeyError):
        api.make_controller("definitely-not-a-controller")


def test_register_custom_controller():
    api.register_controller(
        "test-custom", lambda **kw: api.make_controller("me"),
        overwrite=True)
    assert "test-custom" in api.list_controllers()
    assert api.make_controller("test-custom").name == "ME"


def test_static_controller_rejects_hyperparams():
    with pytest.raises(TypeError):
        api.make_controller("wget/curl", max_ch=64)


def test_ismail_target_rejects_scaling_kwarg():
    with pytest.raises(TypeError):
        api.make_controller("ismail-target", target_tput_mbps=400.0,
                            scaling=True)


def test_as_controller_threads_scaling_to_registry_names():
    ctrl = api.as_controller("me", scaling=False)
    assert ctrl.name == "ME-noscale" and ctrl.scaling is False
    assert api.as_controller("me").name == "ME"
    with pytest.raises(TypeError):            # no load-control module
        api.as_controller("wget/curl", scaling=False)


def test_as_controller_threads_scaling_to_instances():
    base = api.make_controller("me")
    off = api.as_controller(base, scaling=False)
    assert off.name == "ME-noscale" and off.scaling is False
    # default scaling=True never flips an explicit noscale controller back
    noscale = api.make_controller("me", scaling=False)
    assert api.as_controller(noscale).scaling is False
    with pytest.raises(TypeError):            # static protocol instance
        api.as_controller(api.make_controller("http/2"), scaling=False)


def test_scenario_with_bw_schedule_hashes_by_identity():
    bw = np.ones(int(TOTAL_S / 0.1), np.float32)
    a = api.Scenario(profile=CHAMELEON, datasets=FAST, controller="me",
                     total_s=TOTAL_S, bw_schedule=bw)
    b = api.Scenario(profile=CHAMELEON, datasets=FAST, controller="me",
                     total_s=TOTAL_S, bw_schedule=bw)
    assert a == a and a != b          # identity semantics, no ambiguity
    assert len({a, b}) == 2           # hashable despite the array field


def test_noscale_naming():
    assert api.make_controller("me", scaling=False).name == "ME-noscale"
    assert api.make_controller("eemt").name == "EEMT"


def test_avg_tput_mbps_alias_removed():
    r = api.run(api.Scenario(profile=CHAMELEON, datasets=FAST,
                             controller="wget/curl", total_s=TOTAL_S))
    with pytest.raises(AttributeError, match="avg_tput_MBps"):
        r.avg_tput_mbps
    np.testing.assert_allclose(r.avg_tput_gbps,
                               r.avg_tput_MBps * 8.0 / 1000.0)


# --------------------------------------------------------- run vs sweep ---

def _grid():
    scenarios = []
    for prof in (CHAMELEON, CLOUDLAB):
        for name in ("wget/curl", "http/2", "ismail-max-tput", "me", "eemt"):
            scenarios.append(api.Scenario(
                profile=prof, datasets=FAST, controller=_mk(name), cpu=CPU,
                total_s=TOTAL_S))
        scenarios.append(api.Scenario(
            profile=prof, datasets=FAST,
            controller=api.make_controller(
                "eett", target_tput_mbps=prof.bandwidth_mbps * 0.5),
            cpu=CPU, total_s=TOTAL_S))
    return scenarios


def test_sweep_matches_run():
    scenarios = _grid()
    swept = api.sweep(scenarios)
    for sc, batched in zip(scenarios, swept):
        single = api.run(sc)
        assert single.name == batched.name
        assert single.completed == batched.completed
        np.testing.assert_allclose(batched.time_s, single.time_s, rtol=1e-5)
        np.testing.assert_allclose(batched.energy_j, single.energy_j,
                                   rtol=1e-4)
        np.testing.assert_allclose(batched.avg_tput_MBps,
                                   single.avg_tput_MBps, rtol=1e-4)


def test_sweep_batches_shape_compatible_scenarios():
    scenarios = _grid()
    # 12 cells, but controller code paths: static x1, me, eemt, eett -> 4
    assert api.group_count(scenarios) < len(scenarios)
    assert api.group_count(scenarios) == 4


def test_sweep_pads_partition_counts_into_one_group():
    """Scenarios with different dataset counts share one executable: sweep
    pads the partition axis with zero-byte partitions, which are bit-exact
    no-ops on the results."""
    one = (FAST[0],)
    scenarios = [
        api.Scenario(profile=CHAMELEON, datasets=FAST, controller="eemt",
                     cpu=CPU, total_s=TOTAL_S),
        api.Scenario(profile=CHAMELEON, datasets=one, controller="eemt",
                     cpu=CPU, total_s=TOTAL_S),
        api.Scenario(profile=CLOUDLAB, datasets=one, controller="eemt",
                     cpu=CPU, total_s=TOTAL_S),
    ]
    assert api.group_count(scenarios) == 1
    swept = api.sweep(scenarios)
    for sc, batched in zip(scenarios, swept):
        single = api.run(sc)                   # unbatched, unpadded
        assert single.completed == batched.completed
        assert single.time_s == batched.time_s
        assert single.energy_j == batched.energy_j


def test_sweep_preserves_order_and_names():
    scenarios = _grid()
    names = [r.name for r in api.sweep(scenarios)]
    assert names[:3] == ["wget/curl", "http/2", "ismail-max-tput"]


def test_bw_schedule_roundtrip():
    n = int(TOTAL_S / 0.1)
    bw = np.ones(n, np.float32)
    bw[:200] = 0.05                      # throttled while transferring
    r = api.run(api.Scenario(profile=CHAMELEON, datasets=FAST,
                             controller=_mk("eemt"), cpu=CPU,
                             total_s=TOTAL_S, bw_schedule=bw))
    flat = api.run(api.Scenario(profile=CHAMELEON, datasets=FAST,
                                controller=_mk("eemt"), cpu=CPU,
                                total_s=TOTAL_S))
    assert r.energy_j != flat.energy_j or r.time_s != flat.time_s


# ------------------------------------------------------ legacy tombstones ---

def test_legacy_simulate_removed():
    import repro.core
    import repro.core.engine

    with pytest.raises(AttributeError, match=r"repro\.api\.run"):
        repro.core.simulate
    with pytest.raises(AttributeError, match=r"repro\.api\.run"):
        repro.core.engine.simulate
    with pytest.raises(ImportError):
        from repro.core import simulate  # noqa: F401


def test_vmap_parameter_sweep():
    """The engine vectorizes: vmap over initial channel counts."""
    import jax
    import jax.numpy as jnp

    from repro.core import CHAMELEON, MIXED, engine

    ctrl = api.make_controller("eemt", max_ch=64)
    ci = ctrl.init(MIXED, CHAMELEON, CPU)
    base = engine.ScanInputs.from_init(ci, CHAMELEON)
    core = engine.build_core(ctrl.code(), api.as_environment(None).code(),
                             CPU, n_steps=600, dt=0.1, ctrl_every=10)

    def one(num_ch0):
        # Constrained operating point (2 cores @ 1.5 GHz) so the transfer
        # cannot finish inside the window and the knee stays visible.
        ts0 = base.state0._replace(num_ch=num_ch0, prev_num_ch=num_ch0,
                                   cores=jnp.asarray(2, jnp.int32),
                                   freq_idx=jnp.asarray(1, jnp.int32))
        sim, _, _ = core(base._replace(state0=ts0))
        return sim.bytes_moved

    moved = jax.jit(jax.vmap(one))(jnp.asarray([1.0, 8.0, 32.0]))
    assert moved.shape == (3,)
    assert bool((moved > 0).all())
    # Over-concurrency (paper §II): starting at 32 channels triggers the
    # contention knee and moves LESS data in the first minute than a
    # well-sized start — the FSM needs time to shed channels.
    assert float(moved[2]) < float(moved[1])


def test_engine_has_no_controller_special_cases():
    """Acceptance guard: all controller semantics live behind the protocol."""
    import inspect
    from repro.core import engine
    src = inspect.getsource(engine)
    assert "ISMAIL_TARGET" not in src
    assert "isinstance(controller, StaticController)" not in src
