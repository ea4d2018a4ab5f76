"""The engine's main-path programs compile for a TPU v5e at real sizes.

Nothing runs: each program is lowered against shapes placed on a
*described* ``v5e:2x2`` topology and compiled by the TPU compiler that
ships with JAX, which refuses what the chip would refuse (unaligned
blocks, unsupported primitives, programs that do not fit in HBM).  The
cores are built with an explicit ``executor="blocked"`` because
``resolve_executor`` sees this process's CPU backend.

Sizes are the on-chip smoke run's (``chip_smoke.py``): the Figure 2 grid's
widest sweep groups at their full horizon, and the fleet phase's slot
pools.  The persistent compilation cache is off around these compiles: an
entry written for a described chip cannot be read back without one.
"""
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from benchmarks import fig2  # noqa: E402
from benchmarks import fleet as fleet_bench  # noqa: E402
from repro.api import scenario as _scenario  # noqa: E402
from repro.core import engine, tickstate  # noqa: E402
from repro.distributed import sharding  # noqa: E402
from repro.fleet import OnlineConfig  # noqa: E402
from repro.fleet.admission import Combo  # noqa: E402

HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, placement):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                       sharding=placement), tree)


def _fits(compiled) -> None:
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 0 < used <= HBM_BYTES, used


def _grid_groups():
    """The Figure 2 grid's sweep groups, as ``sweep`` forms them."""
    preps = [_scenario._prepare(c.scenario)
             for c in fig2.experiment(smoke=False).cells()]
    merged = _scenario._merged_partition_counts([p.key for p in preps])
    groups: dict = {}
    for p in preps:
        p = _scenario._pad_partitions(p, merged[p.key])
        groups.setdefault(p.key, []).append(p.inputs)
    return groups


def _widest_group(which):
    """The widest full-horizon group of the grid (static tools, 32 lanes)
    or of a tuning controller (its FSM in the tick), stacked."""
    groups = _grid_groups()
    n_steps = max(k.n_steps for k in groups)
    tuned = [k for k in groups if k.ctrl_code.tunes]
    pool = tuned if which == "tuned" else list(groups)
    key = max((k for k in pool if k.n_steps == n_steps),
              key=lambda k: len(groups[k]))
    assert key.n_partitions == max(k.n_partitions for k in groups)
    return key, jax.tree.map(lambda *xs: np.stack(xs), *groups[key])


@pytest.mark.parametrize("which", ["widest", "tuned"])
def test_sweep_runner_compiles_at_fig2_size(one_chip, which):
    """The vmapped blocked sweep runner, trace-free as ``sweep`` runs it,
    at the grid's widest partition count and full horizon.  The grid sets
    no ``bw_schedule``, so each lane carries one bandwidth share."""
    key, stacked = _widest_group(which)
    assert not key.scheduled
    assert np.shape(stacked.bw) == np.shape(stacked.total_mb)[:1]
    runner = engine.get_runner(key.ctrl_code, key.env_code, key.cpu,
                               key.n_steps, key.dt, key.ctrl_every,
                               batched=True, traces=False,
                               executor="blocked")
    compiled = runner.lower(_shapes(stacked, one_chip)).compile()
    _fits(compiled)


def test_scheduled_sweep_runner_compiles_at_fig2_size(one_chip):
    """The same runner on the other input rank: the widest group with an
    [n_steps] bandwidth schedule per lane (``api.tune``'s rungs set one)."""
    key, stacked = _widest_group("widest")
    lanes = np.shape(stacked.bw)[0]
    stacked = stacked._replace(
        bw=np.ones((lanes, key.n_steps), np.float32))
    runner = engine.get_runner(key.ctrl_code, key.env_code, key.cpu,
                               key.n_steps, key.dt, key.ctrl_every,
                               batched=True, traces=False,
                               executor="blocked")
    compiled = runner.lower(_shapes(stacked, one_chip)).compile()
    _fits(compiled)


def test_sharded_sweep_runner_compiles_on_four_chips(topo):
    """The ``shard_map`` sweep runner ``sweep`` uses across chips, on the
    widest group padded to the device count as ``sweep`` pads it."""
    key, stacked = _widest_group("widest")
    devices = tuple(topo.devices)
    stacked, _ = sharding.pad_batch(stacked, len(devices))
    runner = engine.get_sharded_runner(
        key.ctrl_code, key.env_code, key.cpu, key.n_steps, key.dt,
        key.ctrl_every, devices, executor="blocked")
    lanes = NamedSharding(sharding.batch_mesh(devices), P("batch"))
    _fits(runner.lower(_shapes(stacked, lanes)).compile())


def _fleet_pool():
    """One slot pool of the chip run's fleet phase: its wave-runner key
    and the rows of its widest wave (the whole pool, at this capacity)."""
    trace, hosts = fleet_bench.build(smoke=False)
    capacity = sum(h.slots for h in hosts)
    cfg = OnlineConfig(wave_s=chip_smoke.WAVE_S, dt=chip_smoke.DT,
                       pool_capacity=capacity)
    req = next(r for r in trace if r.controller == "EEMT")
    combo = Combo(req, hosts[0], cfg.dt)
    combo.finalize(cfg.max_partitions)
    lay = tickstate.TickLayout(cfg.max_partitions)
    rows = (np.zeros((capacity, lay.params_size), np.float32),
            np.zeros((capacity,), np.float32),
            np.zeros((capacity, lay.f32_size), np.float32),
            np.zeros((capacity, lay.i32_size), np.int32),
            np.zeros((capacity,), np.int32))
    return combo.key, cfg, rows


def test_donated_wave_runner_compiles_on_one_chip(one_chip):
    (code, env, cpu, ctrl_every), cfg, rows = _fleet_pool()
    runner = engine.get_wave_runner(
        code, env, cpu, int(round(cfg.wave_s / cfg.dt)), cfg.dt, ctrl_every,
        executor="blocked", n_partitions=cfg.max_partitions, donate=True)
    _fits(runner.lower(*_shapes(rows, one_chip)).compile())


def test_sharded_wave_runner_compiles_on_four_chips(topo):
    (code, env, cpu, ctrl_every), cfg, rows = _fleet_pool()
    devices = tuple(topo.devices)
    assert len(devices) == 4
    runner = engine.get_sharded_wave_runner(
        code, env, cpu, int(round(cfg.wave_s / cfg.dt)), cfg.dt, ctrl_every,
        devices, executor="blocked", n_partitions=cfg.max_partitions)
    lanes = NamedSharding(sharding.batch_mesh(devices), P("batch"))
    compiled = runner.lower(*_shapes(rows, lanes)).compile()
    _fits(compiled)
