"""Infrastructure tests: checkpoint/restart, sharding rules, collectives,
data pipeline, decode consistency."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.ckpt import restore_latest, save
from repro.configs import ARCHS, get_config, get_smoke_config
from repro.data import SyntheticSource, batches
from repro.distributed import collectives
from repro.distributed.sharding import param_specs, spec_for
from repro.models import build
from repro.optim import AdamWConfig
from repro.train import init_train_state
from repro.train.trainer import TrainerConfig, train


# ------------------------------------------------------------- ckpt -------

def test_checkpoint_roundtrip_bf16():
    cfg = get_smoke_config("qwen2-0.5b")
    bundle = build(cfg)
    state = init_train_state(bundle, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        save(d, 7, state)
        restored, step = restore_latest(d, state)
        assert step == 7
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32)),
            restored, state)


def test_checkpoint_damaged_falls_back():
    cfg = get_smoke_config("qwen2-0.5b")
    bundle = build(cfg)
    state = init_train_state(bundle, jax.random.PRNGKey(0))
    with tempfile.TemporaryDirectory() as d:
        save(d, 1, state)
        save(d, 2, state)
        # damage the newest checkpoint
        os.truncate(os.path.join(d, "step_2", "arrays.npz"), 16)
        restored, step = restore_latest(d, state)
        assert step == 1 and restored is not None


def test_train_restart_resumes_exactly():
    cfg = get_smoke_config("olmo-1b")
    bundle = build(cfg)
    it = batches(SyntheticSource(cfg.vocab_size, 4096), batch=2, seq=16,
                 tuned=False)
    with tempfile.TemporaryDirectory() as d:
        _, rep1 = train(bundle, AdamWConfig(lr=1e-3, total_steps=12), it,
                        TrainerConfig(total_steps=8, ckpt_dir=d,
                                      ckpt_every=4, log_every=0))
        assert rep1.restored_from == -1
        _, rep2 = train(bundle, AdamWConfig(lr=1e-3, total_steps=12), it,
                        TrainerConfig(total_steps=12, ckpt_dir=d,
                                      ckpt_every=4, log_every=0))
        assert rep2.restored_from == 8
        assert rep2.steps_run == 4


# --------------------------------------------------------- sharding -------

def test_param_specs_cover_all_leaves():
    for arch in ARCHS:
        cfg = get_smoke_config(arch)
        bundle = build(cfg)
        shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
        specs = param_specs(shapes)
        n_leaves = len(jax.tree.leaves(shapes))
        n_specs = len(jax.tree.leaves(
            specs, is_leaf=lambda x: isinstance(x, P)))
        assert n_specs == n_leaves


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_shardable_on_16way_model_axis(arch):
    """Every sharded dim of every FULL-config param must divide by 16 —
    catches config errors without compiling."""
    cfg = get_config(arch)
    bundle = build(cfg)
    shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    specs = param_specs(shapes)

    def check(path, leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax == "model":
                assert dim % 16 == 0, (arch, path, leaf.shape, spec)

    flat_l = jax.tree_util.tree_flatten_with_path(shapes)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_l, flat_s):
        check(path, leaf, spec)


def test_spec_for_rules():
    assert spec_for("embed", 2, False) == P("model", None)
    assert spec_for("blocks/attn/wq", 3, True) == P(None, None, "model")
    assert spec_for("blocks/moe/wg", 4, True) == P(None, "model", None, None)
    assert spec_for("layers/0/rec/wx", 2, False) == P(None, "model")
    assert spec_for("final_norm/scale", 1, False) == P()


# ------------------------------------------------------- collectives ------

def test_int8_compression_roundtrip_bounded_error():
    g = jax.random.normal(jax.random.PRNGKey(0), (256, 64)) * 3.0
    q, s = collectives.compress_int8(g)
    deq = collectives.decompress_int8(q, s)
    err = float(jnp.max(jnp.abs(deq - g)))
    assert err <= float(s) * 0.5 + 1e-6        # half-ulp of the quant grid
    assert q.dtype == jnp.int8


def test_error_feedback_reduces_bias():
    """With error feedback, the accumulated quantization error stays bounded
    instead of growing linearly."""
    g = jax.random.normal(jax.random.PRNGKey(1), (128,)) * 0.01
    errors = None
    acc_q = jnp.zeros_like(g)
    for _ in range(16):
        qs, ss, errors = collectives.compressed_grad_tree(g, errors)
        acc_q = acc_q + collectives.decompress_int8(qs, ss)
    acc_true = g * 16
    rel = float(jnp.linalg.norm(acc_q - acc_true) / jnp.linalg.norm(acc_true))
    assert rel < 0.05


def test_chunked_psum_matches_psum():
    mesh = jax.make_mesh((1,), ("x",))
    x = jnp.arange(8.0)

    def f(x):
        return collectives.chunked_psum(x, "x", num_chunks=4)

    y = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x))


# ------------------------------------------------------------- data -------

def test_synthetic_source_deterministic():
    s = SyntheticSource(1000, 512, seed=3)
    np.testing.assert_array_equal(s.read_shard(5), s.read_shard(5))
    assert s.read_shard(5).max() < 1000


def test_batches_shapes_and_range():
    it = batches(SyntheticSource(100, 4096), batch=4, seq=32, tuned=False)
    b = next(it)
    assert b["tokens"].shape == (4, 32)
    assert b["labels"].shape == (4, 32)
    # labels are the shifted continuation of tokens
    arr_t = np.asarray(b["tokens"])
    arr_l = np.asarray(b["labels"])
    np.testing.assert_array_equal(arr_t[:, 1:], arr_l[:, :-1])


def test_tuned_fetcher_produces_and_tunes():
    from repro.core.types import SLA, SLAPolicy
    from repro.data import TunedFetcher
    f = TunedFetcher(SyntheticSource(100, 65536),
                     SLA(policy=SLAPolicy.MAX_THROUGHPUT, timeout_s=0.05,
                         max_ch=8)).start()
    it = f.shards()
    for _ in range(20):
        next(it)
    import time
    deadline = time.monotonic() + 20.0   # first controller tick pays jax
    while f.stats.energy_j == 0 and time.monotonic() < deadline:
        time.sleep(0.1)                  # dispatch latency; wait it out
    stats = f.stats
    f.stop()
    assert stats.bytes_fetched > 0
    assert 1 <= stats.workers <= 8
    assert stats.energy_j > 0


# ------------------------------------------------- decode consistency -----

@pytest.mark.parametrize("arch", ["qwen2-0.5b", "qwen3-moe-30b-a3b",
                                  "rwkv6-7b", "recurrentgemma-2b"])
def test_prefill_decode_matches_teacher_forced(arch):
    cfg = get_smoke_config(arch)
    bundle = build(cfg)
    params = bundle.init_params(jax.random.PRNGKey(0))
    T = 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, T), 0,
                              cfg.vocab_size)
    full, _, _ = bundle.forward(params, toks)

    state = bundle.init_decode_state(2, T)
    outs = []
    for t in range(T):
        logits, state, _ = bundle.forward(
            params, toks[:, t:t + 1], positions=jnp.full((2, 1), t),
            **{bundle.state_kwarg: state})
        outs.append(logits)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec, np.float32),
                               np.asarray(full, np.float32),
                               atol=5e-2, rtol=5e-2)


def test_tuned_checkpoint_writer_roundtrip():
    import numpy as np
    import tempfile, os, glob
    from repro.ckpt import TunedCheckpointWriter
    state = {"w": np.random.randn(128, 128).astype(np.float32),
             "b": np.random.randn(64).astype(np.float32)}
    d = tempfile.mkdtemp()
    stats = TunedCheckpointWriter(target_mbps=100.0, max_writers=2,
                                  timeout_s=0.05).write(d, state)
    assert stats["bytes"] == sum(a.nbytes for a in state.values())
    shards = sorted(glob.glob(os.path.join(d, "shard_*.npy")))
    assert len(shards) == 2
    back = [np.load(s) for s in shards]
    flat = [state["b"], state["w"]] if back[0].shape == (64,) else [state["w"], state["b"]]
    for a, b in zip(back, flat):
        np.testing.assert_array_equal(a, b)
