"""RWKV-6 "Finch" (arXiv:2404.05892) — attention-free LM.

Faithful pieces: data-dependent token-shift (ddlerp with low-rank adapters),
data-dependent per-channel decay w_t (lora on the shifted mix), bonus u,
matrix-valued WKV state per head (head_dim 64), gated output with GroupNorm,
squared-ReLU channel mix.

Reference temporal path is a ``lax.scan`` over time; the TPU-optimized
chunked version is the Pallas kernel in repro/kernels/rwkv6 (same math,
validated against this module's ``wkv_scan``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .common import ModelConfig

HEAD_DIM = 64
LORA_MIX = 32
LORA_DECAY = 64


def _heads(cfg: ModelConfig) -> int:
    return cfg.d_model // HEAD_DIM


def init_time_mix(cfg: ModelConfig, key):
    d = cfg.d_model
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    ks = jax.random.split(key, 9)
    s = 1.0 / math.sqrt(d)
    return {
        # ddlerp: 5 targets (r,k,v,g,w): base mu + rank-LORA_MIX adapter
        "mu": jnp.full((5, d), 0.5, jnp.float32),
        "mix_A": (jax.random.normal(ks[0], (5, d, LORA_MIX)) * s).astype(dt),
        "mix_B": (jax.random.normal(ks[1], (5, LORA_MIX, d)) * 0.01).astype(dt),
        # decay: w_t = exp(-exp(w0 + lora(xw)))
        "w0": jnp.full((d,), -6.0, jnp.float32),
        "w_A": (jax.random.normal(ks[2], (d, LORA_DECAY)) * s).astype(dt),
        "w_B": (jax.random.normal(ks[3], (LORA_DECAY, d)) * 0.01).astype(dt),
        "u": jnp.full((d,), 0.5, jnp.float32),            # bonus, [H*hd]
        "wr": (jax.random.normal(ks[4], (d, d)) * s).astype(dt),
        "wk": (jax.random.normal(ks[5], (d, d)) * s).astype(dt),
        "wv": (jax.random.normal(ks[6], (d, d)) * s).astype(dt),
        "wg": (jax.random.normal(ks[7], (d, d)) * s).astype(dt),
        "wo": (jax.random.normal(ks[8], (d, d)) * s).astype(dt),
        "gn_scale": jnp.ones((d,), jnp.float32),
    }


def init_channel_mix(cfg: ModelConfig, key):
    d, ff = cfg.d_model, cfg.d_ff
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "mu_k": jnp.full((d,), 0.5, jnp.float32),
        "mu_r": jnp.full((d,), 0.5, jnp.float32),
        "wk": (jax.random.normal(k1, (d, ff)) / math.sqrt(d)).astype(dt),
        "wv": (jax.random.normal(k2, (ff, d)) / math.sqrt(ff)).astype(dt),
        "wr": (jax.random.normal(k3, (d, d)) / math.sqrt(d)).astype(dt),
    }


def init_block(cfg: ModelConfig, key):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
        "tm": init_time_mix(cfg, k1),
        "ln2": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
        "cm": init_channel_mix(cfg, k2),
    }


def init_params(cfg: ModelConfig, rng):
    ke, kb, kh = jax.random.split(rng, 3)
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    blocks = jax.vmap(lambda k: init_block(cfg, k))(
        jax.random.split(kb, cfg.num_layers))
    return {
        "embed": (jax.random.normal(ke, (cfg.vocab_size, cfg.d_model)) * 0.02).astype(dt),
        "ln0": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
        "blocks": blocks,
        "ln_out": {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                   "bias": jnp.zeros((cfg.d_model,), jnp.float32)},
        "head": (jax.random.normal(kh, (cfg.d_model, cfg.vocab_size)) * 0.02).astype(dt),
    }


def _ln(p, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    return ((xf - mu) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]).astype(x.dtype)


def _group_norm(x, scale, H, eps=1e-5):
    """Per-head groupnorm of the wkv output. x [B,T,D] viewed [B,T,H,hd]."""
    B, T, D = x.shape
    xf = x.reshape(B, T, H, D // H).astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mu) * lax.rsqrt(var + eps)
    return (y.reshape(B, T, D) * scale).astype(x.dtype)


def time_shift(x, last=None):
    """[B,T,D] -> previous token's activation (zeros / carried ``last``)."""
    first = jnp.zeros_like(x[:, :1]) if last is None else last[:, None]
    return jnp.concatenate([first, x[:, :-1]], axis=1)


def ddlerp(p, x, xs):
    """Data-dependent token-shift mixing for the 5 targets (Finch eq. 2-4).

    x, xs: [B,T,D].  Returns [5,B,T,D] (r,k,v,g,w mixes).  The 5x-residual
    tensor is computed in the activation dtype: the fp32 `mu` broadcast was
    materializing 5 x [B,T,D] fp32 per layer (§Perf cell D)."""
    dx = xs - x
    mu = p["mu"].astype(x.dtype)[:, None, None, :]
    base = x[None] + dx[None] * mu                            # [5,B,T,D]
    t = jnp.tanh(jnp.einsum("btd,sdr->sbtr", x + 0.5 * dx, p["mix_A"]))
    lo = jnp.einsum("sbtr,srd->sbtd", t, p["mix_B"])          # dd adapter
    return (base + lo * dx[None]).astype(x.dtype)


def _head_shard(x, spec_dims):
    """Constrain the head dim of wkv tensors to the 'model' axis — the scan
    carry otherwise blocks GSPMD propagation and the (f32!) scan inputs get
    all-gathered head-replicated (measured 25.8 GB on a 2-layer probe)."""
    try:
        m = jax.sharding.get_abstract_mesh()
        if m.empty or dict(m.shape).get("model", 1) <= 1:
            return x
        if x.shape[spec_dims.index("model")] % dict(m.shape)["model"] != 0:
            return x
        from jax.sharding import PartitionSpec as P
        dp = tuple(a for a in ("pod", "data") if a in m.axis_names)
        spec = P(*[dp if d == "dp" else (d if d == "model" else None)
                   for d in spec_dims])
        return jax.lax.with_sharding_constraint(x, spec)
    except Exception:
        return x


def wkv_scan(r, k, v, w, u):
    """Reference WKV recurrence.

    r,k,v,w: [B,T,H,hd] (w = per-step decay in (0,1)); u: [H,hd].
    y_t = r_t · (S_t + (u⊙k_t) ⊗ v_t);  S_{t+1} = diag(w_t) S_t + k_t ⊗ v_t
    Returns (y [B,T,H,hd], S_final [B,H,hd,hd]).

    The scan xs stay in the activation dtype (cast per step) and are
    explicitly head-sharded over 'model'.
    """
    B, T, H, hd = r.shape
    uf = u.astype(jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = (a.astype(jnp.float32) for a in inp)
        att = jnp.einsum("bhi,bhij->bhj", rt, S)
        bonus = jnp.einsum("bhi,bhi->bh", rt, uf[None] * kt)
        y = att + bonus[..., None] * vt
        S = wt[..., None] * S + kt[..., None] * vt[..., None, :]
        return S, y

    # r/k/v travel in the activation dtype; the decay w stays fp32 (bf16
    # decays near 1.0 lose the long-range memory the data-dependent decay
    # exists for).  Only the carry S0 is constrained: constraining the xs
    # too forced a T->H reshard per tensor per layer (+40% collective bytes,
    # measured) while the carry constraint alone fixes the H-replication.
    xs = tuple(a.transpose(1, 0, 2, 3) for a in (r, k, v, w))
    # S0 head-sharding: +5.5s collectives but peak memory 15.4 -> 6.6 GB/dev
    # (the fit matters; on TPU the Pallas wkv kernel carries S in VMEM and
    # sidesteps the tradeoff entirely).  Full sweep in EXPERIMENTS.md §Perf.
    S0 = _head_shard(jnp.zeros((B, H, hd, hd), jnp.float32),
                     ("dp", "model", None, None))
    S, ys = lax.scan(step, S0, xs)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), S


def time_mix(cfg: ModelConfig, p, x, shift_last=None, S0=None):
    """Full Finch time-mix. Returns (y, (last_token, S_final))."""
    B, T, D = x.shape
    H = _heads(cfg)
    xs = time_shift(x, shift_last)
    mixed = ddlerp(p, x, xs).astype(x.dtype)                  # [5,B,T,D]
    xr, xk, xv, xg, xw = mixed

    r = (xr @ p["wr"]).reshape(B, T, H, HEAD_DIM)
    k = (xk @ p["wk"]).reshape(B, T, H, HEAD_DIM)
    v = (xv @ p["wv"]).reshape(B, T, H, HEAD_DIM)
    g = xg @ p["wg"]

    dec = p["w0"] + jnp.tanh(xw @ p["w_A"]).astype(jnp.float32) @ p["w_B"].astype(jnp.float32)
    w = jnp.exp(-jnp.exp(dec)).reshape(B, T, H, HEAD_DIM)     # (0,1)

    u = p["u"].reshape(H, HEAD_DIM)
    if S0 is None:
        y, S = wkv_scan(r, k, v, w, u)
    else:
        y, S = wkv_scan_with_state(r, k, v, w, u, S0)
    y = _group_norm(y.reshape(B, T, D), p["gn_scale"], H)
    y = (y * jax.nn.silu(g)) @ p["wo"]
    return y, (x[:, -1], S)


def wkv_scan_with_state(r, k, v, w, u, S0):
    B, T, H, hd = r.shape
    rf, kf, vf, wf = (a.astype(jnp.float32) for a in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(S, inp):
        rt, kt, vt, wt = inp
        att = jnp.einsum("bhi,bhij->bhj", rt, S)
        bonus = jnp.einsum("bhi,bhi->bh", rt, uf[None] * kt)
        y = att + bonus[..., None] * vt
        S = wt[..., None] * S + kt[..., None] * vt[..., None, :]
        return S, y

    xs = tuple(a.transpose(1, 0, 2, 3) for a in (rf, kf, vf, wf))
    S, ys = lax.scan(step, S0.astype(jnp.float32), xs)
    return ys.transpose(1, 0, 2, 3).astype(r.dtype), S


def channel_mix(cfg: ModelConfig, p, x, shift_last=None):
    xs = time_shift(x, shift_last)
    xk = (x + (xs - x) * p["mu_k"]).astype(x.dtype)
    xr = (x + (xs - x) * p["mu_r"]).astype(x.dtype)
    k = jnp.square(jax.nn.relu(xk @ p["wk"]))
    return jax.nn.sigmoid(xr @ p["wr"]) * (k @ p["wv"]), x[:, -1]


def block_fwd(cfg: ModelConfig, p, x, state=None):
    """state = (tm_last, S, cm_last) or None."""
    if cfg.seq_parallel and state is None:
        from . import layers as L
        x = L.residual_shard(x)
    tm_last = S0 = cm_last = None
    if state is not None:
        tm_last, S0, cm_last = state
    h, (tm_last2, S2) = time_mix(cfg, p["tm"], _ln(p["ln1"], x), tm_last, S0)
    x = x + h
    h, cm_last2 = channel_mix(cfg, p["cm"], _ln(p["ln2"], x), cm_last)
    x = x + h
    return x, (tm_last2, S2, cm_last2)


def forward(cfg: ModelConfig, params, tokens, *, states=None,
            logits_slice=None, **_):
    """states: stacked per-layer (tm_last [L,B,D], S [L,B,H,hd,hd],
    cm_last [L,B,D]) or None. Returns (logits, new_states, aux=0)."""
    x = _ln(params["ln0"], params["embed"][tokens])

    def blk(bp, x):
        return block_fwd(cfg, bp, x)[0]
    if cfg.remat and states is None:
        from . import layers as L
        blk = jax.checkpoint(blk, policy=L.remat_policy(cfg))

    def body_nostate(x, bp):
        return blk(bp, x), None

    def body_state(x, bp_st):
        bp, st = bp_st
        x, st2 = block_fwd(cfg, bp, x, st)
        return x, st2

    if cfg.unroll_layers:
        def take(tree, i):
            return jax.tree.map(lambda a: a[i], tree)
        sts = []
        for i in range(cfg.num_layers):
            st = take(states, i) if states is not None else None
            if st is None:
                x = blk(take(params["blocks"], i), x)
                st2 = None
            else:
                x, st2 = block_fwd(cfg, take(params["blocks"], i), x, st)
            if states is not None:
                sts.append(st2)
        new_states = (jax.tree.map(lambda *xs: jnp.stack(xs), *sts)
                      if states is not None else None)
    elif states is None:
        x, _ = lax.scan(body_nostate, x, params["blocks"])
        new_states = None
    else:
        x, new_states = lax.scan(body_state, x, (params["blocks"], states))

    x = _ln(params["ln_out"], x)
    if logits_slice is not None:
        x = x[:, -logits_slice:]
    logits = x @ params["head"]
    if states is None:
        from . import layers as L
        logits = L.logits_shard(logits)
    return logits, new_states, jnp.zeros((), jnp.float32)


def init_states(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16):
    H = _heads(cfg)
    L, D = cfg.num_layers, cfg.d_model
    return (
        jnp.zeros((L, batch, D), dtype),
        jnp.zeros((L, batch, H, HEAD_DIM, HEAD_DIM), jnp.float32),
        jnp.zeros((L, batch, D), dtype),
    )
