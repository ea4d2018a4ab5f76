"""Exercise the dry-run cell builder (input_specs + shardings + lowering)
on a small in-suite mesh, per kind and family."""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import pytest  # noqa: E402

from repro.configs import SHAPES, get_smoke_config  # noqa: E402
from repro.launch.dryrun import build_cell  # noqa: E402
from repro.launch.hlo_stats import collective_bytes, roofline_terms  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    return jax.make_mesh((2, 2), ("data", "model"))


@pytest.mark.parametrize("arch,shape", [
    ("qwen2-0.5b", "train_4k"),
    ("qwen3-moe-30b-a3b", "train_4k"),
    ("rwkv6-7b", "decode_32k"),
    ("recurrentgemma-2b", "long_500k"),
    ("whisper-small", "decode_32k"),
    ("qwen2-vl-2b", "prefill_32k"),
])
def test_cell_lowers_on_small_mesh(mesh, arch, shape):
    cfg = get_smoke_config(arch)
    with jax.set_mesh(mesh):
        jitted, args = build_cell(cfg, shape, mesh, microbatches=2)
        lowered = jitted.lower(*args)       # lowering exercises GSPMD specs
    assert "HloModule" in lowered.as_text()[:200] or lowered is not None


def test_collective_parser():
    hlo = """
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), replica_groups={}
  %ag.1 = bf16[64]{0} all-gather(bf16[16]{0} %y), dimensions={0}
  %a2a = (s8[32]{0}, s8[32]{0}) all-to-all(s8[32]{0} %a, s8[32]{0} %b)
  %other = f32[4]{0} add(f32[4]{0} %c, f32[4]{0} %d)
"""
    out = collective_bytes(hlo)
    assert out["bytes"]["all-reduce"] == 128 * 256 * 4 * 2   # counted 2x
    assert out["bytes"]["all-gather"] == 64 * 2
    assert out["bytes"]["all-to-all"] == 64
    assert out["counts"]["all-reduce"] == 1


def test_roofline_terms_bottleneck():
    t = roofline_terms(197e12, 0.0, 0.0, chips=1)   # 1s of pure compute
    assert t["bottleneck"] == "compute"
    assert abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(0.0, 0.0, 50e9, chips=1)
    assert t["bottleneck"] == "collective"
