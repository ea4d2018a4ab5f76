"""Device-sharded sweeps: correctness on a forced multi-device host.

``XLA_FLAGS=--xla_force_host_platform_device_count=N`` must be set before
jax initializes, so the multi-device assertions run in a subprocess with a
fresh interpreter; the in-process tests cover the helpers and the
single-device fallback.
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.distributed import sharding as shd

_SUBPROCESS_SCRIPT = r"""
import os
# Overwrite (not append): the parent pytest process may carry its own
# --xla_force_host_platform_device_count from unrelated tests, and the
# rightmost repeated flag wins.
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
assert jax.device_count() == 4, jax.devices()

import numpy as np
from repro import api
from repro.api import scenario as _scenario
from repro.core import engine
from repro.core.types import CHAMELEON, CLOUDLAB, DatasetSpec

FAST = (DatasetSpec("a", 200, 400.0, 2.0),
        DatasetSpec("b", 10, 600.0, 60.0))


def group(max_chs):
    return [api.Scenario(profile=CHAMELEON, datasets=FAST,
                         controller=api.make_controller("eemt", max_ch=mc),
                         total_s=60.0, dt=0.25)
            for mc in max_chs]


# The completion tick each swept lane's result is made from, in order.
ticks = []
post = _scenario._postprocess


def spy_post(sim, done_at, prep):
    ticks.append(int(done_at))
    return post(sim, done_at, prep)


_scenario._postprocess = spy_post

# A transfer whose energy sum XLA's CPU backend compiles with one FMA
# fewer where a device runs a single lane.
wget = [api.Scenario(profile=CLOUDLAB, datasets=FAST, controller="wget/curl",
                     total_s=120.0, name=f"w{i}") for i in range(4)]

# 6 lanes in one group -> padded to 8 across 4 devices; 4 lanes -> padded
# to two per device; 3 lanes -> fewer lanes than devices, so unsharded.
for scenarios, sharded in ((group((4, 8, 16, 32, 64, 48)), 1),
                           (group((4, 8, 16, 32)), 1), (wget, 1),
                           (group((4, 8, 16)), 0)):
    assert api.group_count(scenarios) == 1
    engine.clear_runner_caches()
    ticks.clear()
    swept = api.sweep(scenarios)
    assert engine.runner_cache_sizes()["sharded"] == sharded
    assert len(swept) == len(scenarios)
    assert len(ticks) == len(scenarios)
    for sc, batched, tick in zip(scenarios, swept, list(ticks)):
        single = api.run(sc)             # unbatched, single-device path
        assert single.completed == batched.completed
        assert single.time_s == batched.time_s, (single.time_s,
                                                 batched.time_s)
        assert single.energy_j == batched.energy_j
        assert single.avg_tput_MBps == batched.avg_tput_MBps
        assert single.completed and batched.metrics is None
        assert tick == int(np.argmax(single.metrics.done)), tick
print("SHARDED-SWEEP-OK")
"""


@pytest.mark.parametrize("lanes,ndev,sharded", [
    (6, 4, True), (4, 4, True), (3, 4, False), (8, 1, False), (8, 0, False)])
def test_should_shard_needs_a_lane_per_device(lanes, ndev, sharded):
    devices = tuple(range(ndev)) if ndev else None
    assert shd.should_shard(lanes, devices) == sharded


def test_pad_batch_pads_by_repeating_last_row():
    tree = {"a": np.arange(6, dtype=np.float32).reshape(3, 2),
            "b": np.asarray([1.0, 2.0, 3.0], np.float32)}
    padded, b = shd.pad_batch(tree, 4)
    assert b == 3
    assert padded["a"].shape == (4, 2) and padded["b"].shape == (4,)
    np.testing.assert_array_equal(padded["a"][3], padded["a"][2])
    # already aligned -> unchanged object contents
    same, b2 = shd.pad_batch(tree, 3)
    assert b2 == 3
    np.testing.assert_array_equal(same["a"], tree["a"])


def test_pad_batch_zero_fill_appends_drained_rows():
    tree = {"a": np.arange(6, dtype=np.float32).reshape(3, 2),
            "b": np.asarray([1, 2, 3], np.int32)}
    padded, b = shd.pad_batch(tree, 4, fill="zero")
    assert b == 3
    np.testing.assert_array_equal(padded["a"][3], np.zeros(2, np.float32))
    assert padded["b"][3] == 0
    assert padded["b"].dtype == np.int32       # dtype preserved
    with pytest.raises(ValueError):
        shd.pad_batch(tree, 4, fill="mirror")


def test_pad_batch_rejects_ragged_pytrees():
    with pytest.raises(ValueError):
        shd.pad_batch({"a": np.zeros((3, 2)), "b": np.zeros((2,))}, 4)


def test_batch_mesh_defaults_to_local_devices():
    mesh = shd.batch_mesh()
    assert mesh.axis_names == ("batch",)
    assert mesh.shape["batch"] == jax.device_count()


def test_shard_batch_places_on_mesh():
    mesh = shd.batch_mesh()
    d = mesh.shape["batch"]
    tree = {"x": np.zeros((2 * d, 3), np.float32)}
    placed = shd.shard_batch(tree, mesh)
    assert placed["x"].shape == (2 * d, 3)
    np.testing.assert_array_equal(np.asarray(placed["x"]), tree["x"])


def test_sweep_on_forced_multi_device_host():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SHARDED-SWEEP-OK" in proc.stdout
