"""Expert-parallel all-to-all MoE vs the dropless reference."""
import os

import pytest

# needs >1 device along 'model'
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.models import layers as L  # noqa: E402
from repro.models.common import ModelConfig, MoEConfig  # noqa: E402


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (XLA_FLAGS was set too late)")
    return jax.make_mesh((2, 2), ("data", "model"))


def _setup(E=8, k=2, d=32, ff=64):
    cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=d,
                      num_heads=4, num_kv_heads=4, d_ff=ff, vocab_size=64,
                      moe=MoEConfig(num_experts=E, top_k=k, d_ff_expert=ff))
    p = L.init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d), jnp.float32)
    return cfg, p, x


def test_a2a_matches_gmm_with_ample_capacity(mesh):
    from repro.distributed.moe_a2a import moe_a2a
    cfg, p, x = _setup()
    with jax.set_mesh(mesh):
        y_ref, _ = L.moe_gmm(cfg, p, x)
        y_a2a, _ = jax.jit(
            lambda p, x: moe_a2a(cfg, p, x, capacity_factor=8.0))(p, x)
    np.testing.assert_allclose(np.asarray(y_a2a), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)


def test_a2a_tight_capacity_drops_but_stays_finite(mesh):
    from repro.distributed.moe_a2a import moe_a2a
    cfg, p, x = _setup()
    with jax.set_mesh(mesh):
        y, aux = jax.jit(
            lambda p, x: moe_a2a(cfg, p, x, capacity_factor=0.5))(p, x)
    assert not bool(jnp.isnan(y).any())
    assert np.isfinite(float(aux))


def test_a2a_differentiable(mesh):
    from repro.distributed.moe_a2a import moe_a2a
    cfg, p, x = _setup()

    def loss(p, x):
        y, aux = moe_a2a(cfg, p, x, capacity_factor=4.0)
        return jnp.sum(jnp.square(y.astype(jnp.float32))) + aux

    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(p, x)
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
