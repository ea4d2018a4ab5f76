"""Device-sharded fleet waves: correctness on a forced multi-device host.

Mirrors tests/test_sharded_sweep.py: the multi-device assertions run in a
subprocess (XLA device-count flags must precede jax init) and compare the
sharded wave path against the single-device path lane by lane.
"""
import os
import subprocess
import sys

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import jax
assert jax.device_count() == 4, jax.devices()

from repro import fleet
from repro.core import engine
from repro.core.types import CHAMELEON, MIXED, DatasetSpec

BIG = (DatasetSpec("a", 2000, 4000.0, 2.0),)
MIX = tuple(DatasetSpec(d.name, d.num_files // 100, d.total_mb / 100,
                        d.avg_file_mb) for d in MIXED)
hosts = fleet.host_pool(6, nic_mbps=1e9)
# 6 lanes -> width 8 over 4 devices; 4 lanes -> one per device (the
# multi-partition datasets are where a batch-width-dependent partition sum
# would show); 3 lanes -> fewer lanes than devices, so unsharded.
for n, datasets, sharded in ((6, BIG, 1), (4, MIX, 1), (3, MIX, 0)):
    reqs = [fleet.TransferRequest(arrival_s=0.0, datasets=datasets,
                                  controller="eemt", profile=CHAMELEON,
                                  name=f"t{i}", total_s=300.0)
            for i in range(n)]
    engine.clear_runner_caches()
    multi = fleet.run_fleet(reqs, hosts, wave_s=5.0, dt=0.1)
    assert engine.runner_cache_sizes()["sharded_wave"] == sharded, n
    single = fleet.run_fleet(reqs, hosts, wave_s=5.0, dt=0.1,
                             devices=jax.devices()[:1])
    assert multi.completed == len(reqs)
    for m, s in zip(multi.transfers, single.transfers):
        assert m == s, (m, s)           # frozen dataclass: bit-exact
print("SHARDED-FLEET-OK")
"""


def test_fleet_on_forced_multi_device_host():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "SHARDED-FLEET-OK" in proc.stdout
