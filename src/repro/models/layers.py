"""Shared neural layers for all assigned architectures (functional style).

Conventions:
  * params are nested dicts of jnp arrays; every init_* returns such a dict
  * activations are [B, T, D] bf16 (configurable), math in fp32 where it
    matters (softmax, norms, router)
  * attention uses a flash-style *chunked* path for long sequences so the
    S x S score matrix is never materialized (the Pallas kernel in
    repro.kernels is the TPU-optimized version of the same schedule; this is
    the XLA fallback that the multi-pod dry-run lowers)
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .common import ModelConfig

NEG_INF = -2.0e38


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _model_axis_size():
    """Size of the 'model' mesh axis in the current mesh context (or None)."""
    try:
        m = jax.sharding.get_abstract_mesh()
        if m.empty:
            return None
        return dict(m.shape).get("model")
    except Exception:
        return None


def _dp_axes():
    m = jax.sharding.get_abstract_mesh()
    return tuple(a for a in ("pod", "data") if a in m.axis_names)


def logits_shard(x):
    """Constrain [B, T, V] logits to vocab-sharding over 'model' (full T per
    device).  Without it GSPMD replicated fp32 logits for the CE chunks
    (measured: 16 copies of 2.1 GB on yi-9b train)."""
    from jax.sharding import PartitionSpec as P
    m = jax.sharding.get_abstract_mesh()
    if m.empty:
        return x
    msize = dict(m.shape).get("model")
    if not msize or msize <= 1 or x.ndim != 3:
        return x
    v = "model" if x.shape[2] % msize == 0 else None
    return jax.lax.with_sharding_constraint(x, P(_dp_axes(), None, v))


def remat_policy(cfg: ModelConfig):
    """'nothing' recomputes the whole block in backward (saves only the
    block inputs — with sequence-parallel residuals that is tiny); 'dots'
    is XLA's dots_with_no_batch_dims_saveable (saves every matmul output:
    measured 19 x 1.08 GB stacked saves on yi-9b train)."""
    if getattr(cfg, "remat_save", "nothing") == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    return jax.checkpoint_policies.nothing_saveable


def residual_shard(x):
    """Megatron-style sequence parallelism for the residual stream:
    constrain [B, T, D] to shard T over 'model' at layer boundaries.  The
    big win is on saved activations: the per-layer scan carry that remat
    keeps for backward shrinks by the model-axis size (measured: 25.7 GB ->
    1.6 GB/device on yi-9b train_4k).  Token-wise ops (norms, row matmuls)
    partition over T for free; GSPMD inserts the all-to-all at the
    attention head boundary and the reduce-scatter after row-parallel
    matmuls, exactly as in hand-written Megatron SP."""
    from jax.sharding import PartitionSpec as P
    m = jax.sharding.get_abstract_mesh()
    if m.empty:
        return x
    msize = dict(m.shape).get("model")
    if not msize or msize <= 1 or x.ndim != 3 or x.shape[1] % msize != 0:
        return x
    return jax.lax.with_sharding_constraint(x, P(_dp_axes(), "model", None))


def _cp_shard(x, *, seq: bool):
    """Context-parallel constraint for attention activations [B,T,H,hd] when
    the head count does not divide the model axis: queries (and the output)
    shard their sequence dim over 'model'; keys/values stay batch-sharded
    and model-replicated (every device needs the full causal prefix).

    Without a consistent constraint GSPMD partially shards the head dim and
    all-reduces score-sized tensors (measured: 3.8 GB/layer on qwen2's
    14 heads @ 16-way model); with batch-only sharding it replicates the
    attention FLOPs model-axis-wide (16x redundant compute)."""
    from jax.sharding import PartitionSpec as P
    dp = _dp_axes()
    spec = P(dp, "model" if seq else None, None, None)
    return jax.lax.with_sharding_constraint(x, spec)


# ----------------------------------------------------------------- norms ---

def init_norm(cfg: ModelConfig, d: int):
    if cfg.norm_type == "ln_nonparam":        # olmo: no learnable affine
        return {}
    if cfg.norm_type == "ln":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def _norm_impl(norm_type: str, p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if norm_type in ("ln", "ln_nonparam"):
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
        if norm_type == "ln":
            y = y * p["scale"] + p["bias"]
    else:                                      # rmsnorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * lax.rsqrt(ms + eps) * p["scale"]
    return y.astype(x.dtype)


def apply_norm(cfg: ModelConfig, p, x, eps: float = 1e-6):
    # (A custom-vjp variant casting cotangents to bf16 was tried and
    # REFUTED as a collective-bytes win — see EXPERIMENTS.md §Perf.)
    return _norm_impl(cfg.norm_type, p, x, eps)


def rms_head_norm(x, scale, eps: float = 1e-6):
    """qk-norm (qwen3): RMS-normalize each head vector."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(ms + eps) * scale).astype(x.dtype)


# ------------------------------------------------------------------ rope ---

def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions [..., T] -> cos/sin [..., T, head_dim//2] (fp32)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B, T, H, hd]; cos/sin broadcastable to [B, T, 1, hd//2]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


def mrope_cos_sin(positions3, sections, head_dim: int, theta: float):
    """M-RoPE (qwen2-vl): positions3 [3, B, T] (t/h/w), section split of the
    rotary dims.  Returns cos/sin [B, T, 1, hd//2]."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions3[..., None].astype(jnp.float32) * freqs   # [3, B, T, half]
    idx = []
    for i, s in enumerate(sections):
        idx += [i] * s
    idx = jnp.asarray(idx[:half], jnp.int32)                  # section of dim
    sel = jax.nn.one_hot(idx, 3, dtype=jnp.float32).T         # [3, half]
    ang = jnp.einsum("sbth,sh->bth", ang, sel)
    return jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]


# ------------------------------------------------------------- attention ---

def init_attention(cfg: ModelConfig, key, cross: bool = False):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.num_heads, cfg.num_kv_heads
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = 1.0 / math.sqrt(d)
    dt = _dtype(cfg)
    p = {
        "wq": (jax.random.normal(k1, (d, h * hd)) * s).astype(dt),
        "wk": (jax.random.normal(k2, (d, hkv * hd)) * s).astype(dt),
        "wv": (jax.random.normal(k3, (d, hkv * hd)) * s).astype(dt),
        "wo": (jax.random.normal(k4, (h * hd, d)) * s).astype(dt),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((h * hd,), dt)
        p["bk"] = jnp.zeros((hkv * hd,), dt)
        p["bv"] = jnp.zeros((hkv * hd,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), jnp.float32)
        p["k_norm"] = jnp.ones((hd,), jnp.float32)
    return p


def _qkv(cfg: ModelConfig, p, x, xkv=None):
    hd = cfg.resolved_head_dim
    xkv = x if xkv is None else xkv
    q = x @ p["wq"]
    k = xkv @ p["wk"]
    v = xkv @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    B, T = x.shape[:2]
    Tk = xkv.shape[1]
    q = q.reshape(B, T, cfg.num_heads, hd)
    k = k.reshape(B, Tk, cfg.num_kv_heads, hd)
    v = v.reshape(B, Tk, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = rms_head_norm(q, p["q_norm"])
        k = rms_head_norm(k, p["k_norm"])
    return q, k, v


def _repeat_kv(k, num_heads):
    """[B, T, Hkv, hd] -> [B, T, H, hd] by repeating each kv head."""
    B, T, hkv, hd = k.shape
    rep = num_heads // hkv
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def attention_scores_full(q, k, v, mask_bias):
    """Reference full-matrix attention, GQA-grouped.

    q [B,Tq,H,hd]; k/v [B,Tk,Hkv,hd] are NOT head-repeated: the einsums are
    grouped so repeated K/V never materialize (repeat_kv made GSPMD
    all-gather H-sized f32 K/V tensors — 5.4 GB/layer on qwen3-moe).
    mask_bias: broadcastable to [B,1,1,Tq,Tk]."""
    B, Tq, H, hd = q.shape
    hkv = k.shape[2]
    rep = H // hkv
    qg = q.reshape(B, Tq, hkv, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32)
    s = s / math.sqrt(hd) + mask_bias
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrqk,bkgd->bqgrd", w, v)
    return o.reshape(B, Tq, H, hd)


def attention_chunked(q, k, v, *, causal: bool, window: int, q_chunk: int,
                      q_offset=0):
    """Flash-style chunked attention in pure XLA (static loop over query
    blocks), GQA-grouped (k/v un-repeated).

    Never materializes the full [T, T] score matrix; peak extra memory is
    [B, Hkv, rep, q_chunk, Tk].  This is the schedule the Pallas kernel
    implements natively on TPU; here it is the portable fallback that the
    dry-run lowers.
    """
    B, Tq, H, hd = q.shape
    Tk, hkv = k.shape[1], k.shape[2]
    rep = H // hkv
    nchunk = max(Tq // q_chunk, 1)
    q_chunk = Tq // nchunk
    scale = 1.0 / math.sqrt(hd)

    outs = []
    for i in range(nchunk):
        qc = lax.slice_in_dim(q, i * q_chunk, (i + 1) * q_chunk, axis=1)
        qg = qc.reshape(B, q_chunk, hkv, rep, hd)
        lo, hi = 0, Tk
        if causal and isinstance(q_offset, int):
            # Only reachable keys: [max(0, chunk_lo - window), chunk_hi).
            hi = min(Tk, q_offset + (i + 1) * q_chunk)
            if window > 0:
                lo = max(0, q_offset + i * q_chunk - window + 1)
            lo = (lo // 128) * 128          # keep slices lane-aligned
        kc = lax.slice_in_dim(k, lo, hi, axis=1)
        vc = lax.slice_in_dim(v, lo, hi, axis=1)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kc).astype(jnp.float32) * scale
        if causal:
            qpos = q_offset + i * q_chunk + jnp.arange(q_chunk)
            kp = lo + jnp.arange(hi - lo)
            m = kp[None, :] > qpos[:, None]
            if window > 0:
                m |= kp[None, :] <= (qpos[:, None] - window)
            s = jnp.where(m[None, None, None], NEG_INF, s)
        w = jax.nn.softmax(s, axis=-1).astype(qc.dtype)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", w, vc)
        outs.append(o.reshape(B, q_chunk, H, hd))
    return jnp.concatenate(outs, axis=1)


def attention(cfg: ModelConfig, p, x, positions, *, causal=True, window=0,
              cache=None, xkv=None, mrope_pos=None, q_chunk=2048):
    """Unified attention: train/prefill (cache=None or write) and decode.

    cache: None                      -> plain forward over x
           dict(k, v, idx)           -> decode: append x's kv, attend to cache
    Returns (y [B,T,D], new_cache_or_None).
    """
    q, k, v = _qkv(cfg, p, x, xkv)
    hd = cfg.resolved_head_dim

    if xkv is None and cfg.use_rope:  # self-attention: rotary embed
        if cfg.mrope and mrope_pos is not None:
            cos, sin = mrope_cos_sin(mrope_pos, cfg.mrope_sections, hd,
                                     cfg.rope_theta)
        else:
            cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
            cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        q = apply_rope(q, cos, sin)
        if cache is None:
            kcos, ksin = cos, sin
        else:  # decode: key position == current positions
            kcos, ksin = cos, sin
        k = apply_rope(k, kcos, ksin)

    new_cache = None
    ring = cache is not None and "pos" in cache
    if ring:
        # Ring-buffer cache for windowed attention (bounded memory at 500k
        # context).  Decode-only: T must be 1.
        idx = cache["idx"]
        clen = cache["k"].shape[1]
        slot = idx % clen
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, slot, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, slot, 0, 0))
        cpos = lax.dynamic_update_slice(
            cache["pos"], positions.astype(jnp.int32), (0, slot))
        new_cache = {"k": ck, "v": cv, "pos": cpos, "idx": idx + x.shape[1]}
        k, v = ck, cv
    elif cache is not None and "prow" in cache:
        # Per-row write offsets (continuous batching: each batch slot is at
        # its own position).  Scatter write; causal masking by absolute
        # position makes stale entries from a recycled slot unreachable.
        rows = jnp.arange(x.shape[0])[:, None]
        offs = positions.astype(jnp.int32)
        ck = cache["k"].at[rows, offs].set(k.astype(cache["k"].dtype))
        cv = cache["v"].at[rows, offs].set(v.astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv, "idx": cache["idx"] + x.shape[1],
                     "prow": cache["prow"]}
        k, v = ck, cv
    elif cache is not None:
        idx = cache["idx"]
        ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                      (0, idx, 0, 0))
        cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                      (0, idx, 0, 0))
        new_cache = {"k": ck, "v": cv, "idx": idx + x.shape[1]}
        k, v = ck, cv

    # k/v stay un-repeated ([B,T,Hkv,hd]); the attention einsums are
    # GQA-grouped (see attention_scores_full).
    B, Tq = q.shape[:2]
    Tk = k.shape[1]
    if ring:
        kpos = new_cache["pos"]                              # [B, Clen]
        qpos = positions                                     # [B, Tq]
        dist = qpos[:, :, None] - kpos[:, None, :]
        m = (dist < 0) | (kpos[:, None, :] < 0)
        if window > 0:
            m |= dist >= window
        bias = jnp.where(m[:, None, None], NEG_INF, 0.0)     # [B,1,1,Tq,Clen]
        y = attention_scores_full(q, k, v, bias)
    elif cache is not None:
        # decode / cached attention: causal per-row mask; the plain path
        # additionally hides never-written (zero) slots beyond the shared
        # write index (per-row caches overwrite rows wholesale, so absolute
        # causal masking alone suffices).
        kpos = jnp.arange(Tk)
        qpos = positions  # [B, Tq]
        m = kpos[None, None, :] > qpos[:, :, None]          # causal
        if window > 0:
            m |= kpos[None, None, :] <= (qpos[:, :, None] - window)
        if "prow" not in cache:
            valid = kpos[None, :] < (cache["idx"] + Tq)
            m |= ~valid[:, None, :]
        bias = jnp.where(m[:, None, None], NEG_INF, 0.0)     # [B,1,1,Tq,Tk]
        y = attention_scores_full(q, k, v, bias)
    elif Tq > q_chunk:
        msize = _model_axis_size()
        # Context-parallel attention: q/y stay sequence-sharded, the (small,
        # GQA) K/V are gathered.  Mandatory when heads don't divide the model
        # axis; otherwise opt-in (cfg.cp_attention) — for GQA it replaces the
        # per-layer T->H resharding all-gathers of q (4.3 GB f32/layer on
        # qwen3-moe) with a Hkv-sized K/V gather (67 MB/layer).
        cp = (msize and msize > 1 and Tq % msize == 0
              and (cfg.num_heads % msize != 0
                   or getattr(cfg, "cp_attention", False)))
        if cp:
            q = _cp_shard(q, seq=True)
            k = _cp_shard(k, seq=False)
            v = _cp_shard(v, seq=False)
        y = attention_chunked(q, k, v, causal=causal, window=window,
                              q_chunk=q_chunk)
        if cp:
            y = _cp_shard(y, seq=True)
    else:
        if causal:
            kpos = jnp.arange(Tk)
            qpos = jnp.arange(Tq)
            m = kpos[None, :] > qpos[:, None]
            if window > 0:
                m |= kpos[None, :] <= (qpos[:, None] - window)
            bias = jnp.where(m, NEG_INF, 0.0)[None, None, None]
        else:
            bias = jnp.zeros((1, 1, 1, 1, 1), jnp.float32)
        y = attention_scores_full(q, k, v, bias)

    y = y.reshape(B, Tq, cfg.num_heads * hd) @ p["wo"]
    return y, new_cache


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               ring: bool = False, per_row: bool = False):
    hd = cfg.resolved_head_dim
    c = {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype),
        "idx": jnp.zeros((), jnp.int32),
    }
    if ring:
        c["pos"] = jnp.full((batch, max_len), -1, jnp.int32)
    if per_row:
        c["prow"] = jnp.zeros((), jnp.int32)   # marker: per-row writes
    return c


# ------------------------------------------------------------------- mlp ---

def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dt = _dtype(cfg)
    k1, k2, k3 = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {
            "wg": (jax.random.normal(k1, (d, ff)) * s_in).astype(dt),
            "wu": (jax.random.normal(k2, (d, ff)) * s_in).astype(dt),
            "wd": (jax.random.normal(k3, (ff, d)) * s_out).astype(dt),
        }
    return {  # gelu mlp (whisper)
        "wu": (jax.random.normal(k1, (d, ff)) * s_in).astype(dt),
        "bu": jnp.zeros((ff,), dt),
        "wd": (jax.random.normal(k2, (ff, d)) * s_out).astype(dt),
        "bd": jnp.zeros((cfg.d_model,), dt),
    }


def mlp(cfg: ModelConfig, p, x):
    if cfg.mlp_type == "swiglu":
        return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
    if cfg.mlp_type == "geglu":
        return (jax.nn.gelu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]
    return (jax.nn.gelu(x @ p["wu"] + p["bu"])) @ p["wd"] + p["bd"]


# ------------------------------------------------------------------- moe ---

def init_moe(cfg: ModelConfig, key):
    assert cfg.moe is not None
    m = cfg.moe
    d, ff, E = cfg.d_model, m.d_ff_expert, m.num_experts
    dt = _dtype(cfg)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(ff)
    p = {
        "router": (jax.random.normal(k1, (d, E)) * s_in).astype(jnp.float32),
        "wg": (jax.random.normal(k2, (E, d, ff)) * s_in).astype(dt),
        "wu": (jax.random.normal(k3, (E, d, ff)) * s_in).astype(dt),
        "wd": (jax.random.normal(k4, (E, ff, d)) * s_out).astype(dt),
    }
    if m.num_shared_experts:
        sf = ff * m.num_shared_experts
        p["shared"] = init_mlp(cfg, k5, d_ff=sf)
    return p


def moe_router(cfg: ModelConfig, p, xf):
    """Top-k routing. xf [N, D] -> (weights [N, k], ids [N, k], aux_loss)."""
    m = cfg.moe
    logits = xf.astype(jnp.float32) @ p["router"]           # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    w, ids = lax.top_k(probs, m.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # Switch-style load-balance auxiliary loss.
    E = m.num_experts
    me = jnp.mean(probs, axis=0)                             # mean prob/expert
    ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(me * ce) * m.load_balance_coef
    return w, ids, aux


def moe_gmm(cfg: ModelConfig, p, x):
    """Dropless MoE via sort + lax.ragged_dot (grouped matmul).

    Exactly top_k * (3 d ff) FLOPs per token — the TPU-native analogue of
    megablocks.  Used on single-host paths; the expert-parallel a2a variant
    lives in repro.distributed.moe_a2a.
    """
    m = cfg.moe
    B, T, D = x.shape
    N = B * T
    xf = x.reshape(N, D)
    w, ids, aux = moe_router(cfg, p, xf)

    k = m.top_k
    flat_ids = ids.reshape(-1)                               # [N*k]
    order = jnp.argsort(flat_ids)
    tok = jnp.repeat(jnp.arange(N), k)[order]                # source token
    xs = xf[tok]                                             # [N*k, D]
    group_sizes = jnp.bincount(flat_ids, length=m.num_experts)

    g = lax.ragged_dot(xs, p["wg"], group_sizes)
    u = lax.ragged_dot(xs, p["wu"], group_sizes)
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = lax.ragged_dot(h, p["wd"], group_sizes)              # [N*k, D]

    wflat = w.reshape(-1)[order].astype(y.dtype)
    out = jnp.zeros((N, D), y.dtype).at[tok].add(y * wflat[:, None])

    if m.num_shared_experts:
        out = out + mlp(cfg, p["shared"], xf)
    return out.reshape(B, T, D), aux


def moe_dense(cfg: ModelConfig, p, x):
    """All-experts einsum formulation: E/k x more FLOPs but trivially
    shardable by GSPMD (experts on the model axis).  Used where ragged_dot
    cannot be partitioned."""
    m = cfg.moe
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    w, ids, aux = moe_router(cfg, p, xf)
    mask = jax.nn.one_hot(ids, m.num_experts, dtype=jnp.float32)  # [N,k,E]
    comb = jnp.einsum("nk,nke->ne", w, mask).astype(x.dtype)      # [N,E]

    g = jnp.einsum("nd,edf->enf", xf, p["wg"])
    u = jnp.einsum("nd,edf->enf", xf, p["wu"])
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    y = jnp.einsum("enf,efd->end", h, p["wd"])                    # [E,N,D]
    out = jnp.einsum("end,ne->nd", y, comb)

    if m.num_shared_experts:
        out = out + mlp(cfg, p["shared"], xf)
    return out.reshape(B, T, D), aux
