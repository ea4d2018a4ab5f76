"""Executor parity: the flat-state lowering must be invisible.

Covers the lowering contract (``repro.core.tickstate`` +
``repro.core.engine`` executors): pack/unpack round-trips are bit-exact,
and the ``blocked`` and ``pallas`` (interpret-mode) executors reproduce the
``reference`` executor — and therefore the RUN_GOLDEN values — bit for bit
across run, sweep, fleet, and observed-rollout cells.  ``auto`` is
``blocked`` on every backend, and paths the fused kernel cannot run refuse
an explicit ``pallas`` request.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import api, fleet, learn
from repro.api import scenario as _scenario
from repro.core import engine, tickstate
from repro.core.types import CHAMELEON, CLOUDLAB, CpuProfile, DatasetSpec

CPU = CpuProfile()

FAST = (DatasetSpec("a", 200, 400.0, 2.0),
        DatasetSpec("b", 10, 600.0, 60.0))
ONE = (DatasetSpec("c", 50, 500.0, 10.0),)

# Duplicated verbatim from tests/test_environments.py RUN_GOLDEN (PR 5):
# (completed, time_s, energy_j, avg_tput_MBps, avg_power_w).
GOLDEN_SUBSET = {
    ("chameleon", "eemt", "fast"): (True, 1.2000000000000002, 31.04885482788086, 833.3333333333333, 25.87404568990071),
    ("chameleon", "me", "fast"): (True, 4.0, 47.53553771972656, 249.9999542236328, 11.88388442993164),
    ("chameleon", "wget/curl", "one"): (True, 8.3, 140.1924591064453, 60.24096385542168, 16.89065772366811),
    ("cloudlab", "eett", "one"): (True, 4.2, 57.62987518310547, 119.04764084588913, 13.721398853120348),
}
_PROFILES = {"chameleon": CHAMELEON, "cloudlab": CLOUDLAB}
_DATASETS = {"fast": FAST, "one": ONE}


def _mk(name):
    if name == "eett":
        return api.make_controller(name, target_tput_mbps=400.0)
    return api.make_controller(name)


def _scn(profile, name, ds, **kw):
    kw.setdefault("total_s", 240.0)
    kw.setdefault("dt", 0.1)
    return api.Scenario(profile=profile, datasets=ds, controller=_mk(name),
                        **kw)


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))


# ------------------------------------------------ pack/unpack round-trip ---

def _random_state(rng, p):
    from repro.core.types import SimState, TunerState
    sim = SimState(
        remaining_mb=rng.uniform(0, 1e4, p).astype(np.float32),
        window_mb=rng.uniform(0, 64, p).astype(np.float32),
        t=np.float32(rng.uniform(0, 3600)),
        energy_j=np.float32(rng.uniform(0, 1e5)),
        bytes_moved=np.float32(rng.uniform(0, 1e6)),
    )
    ts = TunerState(
        fsm=np.int32(rng.integers(0, 7)),
        num_ch=np.float32(rng.uniform(1, 64)),
        prev_num_ch=np.float32(rng.uniform(1, 64)),
        ref=np.float32(rng.uniform(0, 1e3)),
        cores=np.int32(rng.integers(1, 9)),
        freq_idx=np.int32(rng.integers(0, 7)),
        acc_mb=np.float32(rng.uniform(0, 1e4)),
        acc_j=np.float32(rng.uniform(0, 1e4)),
        acc_s=np.float32(rng.uniform(0, 60)),
    )
    return sim, ts


@pytest.mark.parametrize("p", [1, 2, 5])
def test_state_roundtrip_bit_exact(p):
    rng = np.random.default_rng(7 * p)
    lay = tickstate.TickLayout(p)
    for _ in range(20):
        sim, ts = _random_state(rng, p)
        f32, i32 = lay.pack_state(sim, ts, xp=np)
        assert f32.shape == (lay.f32_size,) and f32.dtype == np.float32
        assert i32.shape == (lay.i32_size,) and i32.dtype == np.int32
        sim2, ts2 = lay.unpack_state(f32, i32)
        assert _leaves_equal((sim, ts), (sim2, ts2))
        # and on-device (jnp) packing agrees with host (np) packing
        f32j, i32j = lay.pack_state(sim, ts)
        assert np.array_equal(np.asarray(f32j), f32)
        assert np.array_equal(np.asarray(i32j), i32)


@pytest.mark.parametrize("case", sorted(GOLDEN_SUBSET))
def test_params_roundtrip_bit_exact(case):
    pn, cn, dn = case
    prep = _scenario._prepare(_scn(_PROFILES[pn], cn, _DATASETS[dn]))
    p = len(np.asarray(prep.inputs.total_mb))
    lay = tickstate.TickLayout(p)
    row = lay.pack_params(prep.inputs, xp=np)
    assert row.shape == (lay.params_size,)
    fields = lay.unpack_params(row)
    for f in ("net", "sla", "pp", "par", "total_mb", "avg_file_mb",
              "static_w"):
        assert _leaves_equal(getattr(prep.inputs, f), fields[f]), f


def test_unpack_state_is_batched():
    """Ellipsis indexing: a stacked [B, row] batch unpacks to [B]-leaved
    pytrees (the fleet wave path relies on this)."""
    rng = np.random.default_rng(3)
    lay = tickstate.TickLayout(2)
    states = [_random_state(rng, 2) for _ in range(4)]
    rows = [lay.pack_state(s, t, xp=np) for s, t in states]
    f32 = np.stack([r[0] for r in rows])
    i32 = np.stack([r[1] for r in rows])
    sim, ts = lay.unpack_state(f32, i32)
    assert sim.remaining_mb.shape == (4, 2)
    for b, (s, t) in enumerate(states):
        assert _leaves_equal(
            (s, t), jax.tree.map(lambda x: x[b], (sim, ts)))


def test_layout_validates_and_hashes():
    with pytest.raises(ValueError):
        tickstate.TickLayout(0)
    assert tickstate.TickLayout(3) == tickstate.TickLayout(3)
    assert hash(tickstate.TickLayout(3)) == hash(tickstate.TickLayout(3))
    assert tickstate.TickLayout(3) != tickstate.TickLayout(4)


# ------------------------------------------------------ executor registry ---

def test_resolve_executor():
    assert engine.resolve_executor("reference") == "reference"
    assert engine.resolve_executor("auto", backend="cpu") == "blocked"
    assert engine.resolve_executor("auto", backend="tpu") == "blocked"
    assert engine.resolve_executor("auto", backend="tpu",
                                   observe=True) == "blocked"
    # the fused kernel runs interpreted off-TPU and is refused on TPU
    assert engine.resolve_executor("pallas", backend="cpu") == "pallas"
    with pytest.raises(ValueError, match="ROADMAP A2"):
        engine.resolve_executor("pallas", backend="tpu")
    with pytest.raises(ValueError, match="unknown executor"):
        engine.resolve_executor("vectorized")
    with pytest.raises(ValueError, match="observe"):
        engine.resolve_executor("pallas", observe=True)
    with pytest.raises(ValueError, match="unknown executor"):
        api.Scenario(profile=CHAMELEON, datasets=FAST, controller="eemt",
                     executor="typo")


def test_executor_joins_sweep_group_key():
    a = _scn(CHAMELEON, "eemt", FAST)
    b = _scn(CHAMELEON, "eemt", FAST, executor="reference")
    c = _scn(CHAMELEON, "eemt", FAST,
             executor=engine.resolve_executor("auto"))
    ka = _scenario._prepare(a).key
    kb = _scenario._prepare(b).key
    kc = _scenario._prepare(c).key
    assert ka != kb and ka.executor != kb.executor
    assert ka == kc          # "auto" groups with its resolved name


def test_cache_registry_keys_and_clear():
    engine.clear_runner_caches()
    prep = _scenario._prepare(_scn(CHAMELEON, "eemt", FAST))
    k = prep.key
    args = (k.ctrl_code, k.env_code, k.cpu, k.n_steps, k.dt, k.ctrl_every)
    r1 = engine.get_runner(*args, batched=False, executor="reference")
    r2 = engine.get_runner(*args, batched=False, executor="reference")
    assert r1 is r2
    r3 = engine.get_runner(*args, batched=False, executor="blocked")
    assert r3 is not r1
    # "auto" shares the cache entry of its backend resolution
    r4 = engine.get_runner(*args, batched=False, executor="auto")
    assert r4 is engine.get_runner(
        *args, batched=False, executor=engine.resolve_executor("auto"))
    assert engine.runner_cache_sizes()["runner"] == 2
    engine.clear_runner_caches()
    assert sum(engine.runner_cache_sizes().values()) == 0
    assert engine.get_runner(*args, batched=False) is not r1


# ------------------------------------------------------ run/sweep parity ---

@pytest.mark.parametrize("executor", ["reference", "blocked", "pallas"])
def test_run_golden_bit_identity(executor):
    """Every executor reproduces the PR 5 RUN_GOLDEN values exactly
    (pallas in interpret mode on CPU)."""
    for (pn, cn, dn), want in sorted(GOLDEN_SUBSET.items()):
        r = api.run(_scn(_PROFILES[pn], cn, _DATASETS[dn],
                         executor=executor))
        got = (r.completed, r.time_s, r.energy_j, r.avg_tput_MBps,
               r.avg_power_w)
        assert got == want, (executor, pn, cn, dn)


@pytest.mark.parametrize("executor", ["blocked", "pallas"])
def test_full_trace_bit_identity(executor):
    """Not just the scalars: final state and the whole per-tick metrics
    trace match the reference executor bit-for-bit."""
    for case in (("chameleon", "eemt", "fast"), ("cloudlab", "eett", "one")):
        pn, cn, dn = case
        ref = api.run(_scn(_PROFILES[pn], cn, _DATASETS[dn],
                           executor="reference"))
        got = api.run(_scn(_PROFILES[pn], cn, _DATASETS[dn],
                           executor=executor))
        assert _leaves_equal(ref.metrics, got.metrics), case


def test_sweep_golden_bit_identity_blocked():
    cases = sorted(GOLDEN_SUBSET)
    scs = [_scn(_PROFILES[pn], cn, _DATASETS[dn], executor="blocked")
           for pn, cn, dn in cases]
    for (pn, cn, dn), r in zip(cases, api.sweep(scs)):
        got = (r.completed, r.time_s, r.energy_j, r.avg_tput_MBps,
               r.avg_power_w)
        assert got == GOLDEN_SUBSET[(pn, cn, dn)], (pn, cn, dn)


# ----------------------------------------------------------- fleet parity ---

def test_fleet_zero_contention_matches_api_run():
    """A fleet lane that never sees contention is bit-identical to api.run
    of the same scenario, on both wave executors."""
    req = fleet.TransferRequest(arrival_s=0.0, datasets=FAST,
                                controller="eemt", profile=CHAMELEON,
                                name="solo", total_s=240.0)
    hosts = fleet.host_pool(1, nic_mbps=1e9)
    solo = api.run(_scn(CHAMELEON, "eemt", FAST))
    for executor in ("reference", "blocked", "auto"):
        rep = fleet.run_fleet([req], hosts, wave_s=5.0, dt=0.1,
                              executor=executor)
        (t,) = rep.transfers
        assert t.completed
        assert t.time_s == solo.time_s, executor
        assert t.energy_j == solo.energy_j, executor


def test_fleet_executors_identical_under_contention():
    """Reference and blocked wave paths agree transfer-by-transfer on a
    contended multi-host trace (shares < 1.0, queueing, retirement)."""
    reqs = [fleet.TransferRequest(arrival_s=0.3 * i, datasets=FAST,
                                  controller=c, profile=CHAMELEON,
                                  name=f"t{i}-{c}", total_s=240.0)
            for i in range(4) for c in ("eemt", "me")]
    hosts = fleet.host_pool(2, nic_mbps=800.0, slots=3)
    reps = {ex: fleet.run_fleet(reqs, hosts, wave_s=5.0, dt=0.1,
                                executor=ex)
            for ex in ("reference", "blocked")}
    a, b = reps["reference"], reps["blocked"]
    assert a.completed == b.completed
    for ta, tb in zip(a.transfers, b.transfers):
        assert (ta.name, ta.time_s, ta.energy_j, ta.moved_mb,
                ta.completed) == (tb.name, tb.time_s, tb.energy_j,
                                  tb.moved_mb, tb.completed)


# -------------------------------------------------- observed rollout lane ---

def test_observed_rollout_bit_identity_across_executors():
    """run_observed on blocked == reference: same final state, metrics, and
    Observation trace (the hook reads the same per-tick values)."""
    runs = {}
    for ex in ("reference", "blocked"):
        (run,) = learn.run_observed(
            [_scn(CHAMELEON, "eemt", FAST, executor=ex)])
        runs[ex] = run
    a, b = runs["reference"], runs["blocked"]
    assert _leaves_equal(a.sim, b.sim)
    assert _leaves_equal(a.metrics, b.metrics)
    assert _leaves_equal(a.obs, b.obs)


def test_observed_refuses_pallas_and_auto_matches_reference():
    """No fallback: an explicit pallas scenario raises in run_observed (the
    fused kernel emits no Observation traces) instead of being swapped for
    blocked; the auto scenario runs observed, equal to the reference."""
    with pytest.raises(ValueError, match="observe"):
        learn.run_observed([_scn(CHAMELEON, "me", FAST, executor="pallas")])
    (ref,) = learn.run_observed(
        [_scn(CHAMELEON, "me", FAST, executor="reference")])
    (got,) = learn.run_observed([_scn(CHAMELEON, "me", FAST)])
    assert _leaves_equal(ref.obs, got.obs)


@pytest.mark.parametrize("path", ["run_fleet", "run_fleet_online",
                                  "wave_runner", "sharded_runner"])
def test_explicit_pallas_is_refused_where_the_kernel_cannot_run(path):
    """Paths the fused kernel does not reach raise on an explicit pallas
    request; none swaps it for another executor."""
    req = fleet.TransferRequest(arrival_s=0.0, datasets=FAST,
                                controller="eemt", profile=CHAMELEON,
                                name="solo", total_s=240.0)
    hosts = fleet.host_pool(1)
    k = _scenario._prepare(_scn(CHAMELEON, "eemt", FAST)).key
    args = (k.ctrl_code, k.env_code, k.cpu)
    calls = {
        "run_fleet": lambda: fleet.run_fleet([req], hosts,
                                             executor="pallas"),
        "run_fleet_online": lambda: fleet.run_fleet_online(
            [req], hosts, executor="pallas"),
        "wave_runner": lambda: engine.get_wave_runner(
            *args, 50, 0.1, k.ctrl_every, executor="pallas",
            n_partitions=2),
        "sharded_runner": lambda: engine.get_sharded_runner(
            *args, k.n_steps, 0.1, k.ctrl_every, tuple(jax.devices()),
            executor="pallas"),
    }
    with pytest.raises(ValueError, match="pallas"):
        calls[path]()


# ------------------------------------------------- sharded blocked waves ---

_SUBPROCESS_SCRIPT = r"""
import os
# Overwrite (not append): the parent pytest process may carry its own
# --xla_force_host_platform_device_count from unrelated tests, and the
# rightmost repeated flag wins.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
assert jax.device_count() == 4, jax.devices()

from repro import fleet
from repro.core.types import CHAMELEON, DatasetSpec

BIG = (DatasetSpec("a", 2000, 4000.0, 2.0),)
reqs = [fleet.TransferRequest(arrival_s=0.0, datasets=BIG,
                              controller="eemt", profile=CHAMELEON,
                              name=f"t{i}", total_s=300.0)
        for i in range(6)]
hosts = fleet.host_pool(6, nic_mbps=1e9)
multi = fleet.run_fleet(reqs, hosts, wave_s=5.0, dt=0.1,
                        executor="blocked")
single = fleet.run_fleet(reqs, hosts, wave_s=5.0, dt=0.1,
                         devices=jax.devices()[:1], executor="blocked")
assert multi.completed == len(reqs)
for m, s in zip(multi.transfers, single.transfers):
    assert (m.time_s, m.energy_j, m.completed) == \
        (s.time_s, s.energy_j, s.completed), (m, s)
print("SHARDED-BLOCKED-OK")
"""


def test_blocked_waves_on_forced_multi_device_host():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SHARDED-BLOCKED-OK" in proc.stdout
