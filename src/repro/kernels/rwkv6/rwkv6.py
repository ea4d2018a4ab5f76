"""RWKV-6 WKV recurrence — Pallas TPU kernel.

Grid (B, H, nT) with the time axis innermost/sequential; the matrix-valued
state S [hd, hd] lives in fp32 VMEM scratch and is carried across time
chunks, so HBM traffic is exactly one read of (r,k,v,w) and one write of y —
the recurrence never round-trips state through HBM (the XLA scan fallback
carries S through the loop as an HBM-resident carry).

Within a chunk the update is the faithful per-step form:
    y_t = r_t S_t + (r_t · (u ⊙ k_t)) v_t
    S_{t+1} = diag(w_t) S_t + k_t ⊗ v_t
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_scr, *,
                bt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)      # [bt, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    w = w_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)         # [1, hd] -> [hd]

    def step(t, carry):
        S, ybuf = carry
        rt = lax.dynamic_slice_in_dim(r, t, 1, 0)        # [1, hd]
        kt = lax.dynamic_slice_in_dim(k, t, 1, 0)
        vt = lax.dynamic_slice_in_dim(v, t, 1, 0)
        wt = lax.dynamic_slice_in_dim(w, t, 1, 0)
        att = rt @ S                                     # [1, hd]
        bonus = jnp.sum(rt * u * kt, axis=1, keepdims=True)  # [1,1]
        yt = att + bonus * vt
        S = wt.T * S + kt.T @ vt                         # [hd, hd]
        ybuf = lax.dynamic_update_slice_in_dim(ybuf, yt, t, 0)
        return S, ybuf

    S0 = s_scr[...]
    ybuf0 = jnp.zeros_like(r)
    S, ybuf = lax.fori_loop(0, bt, step, (S0, ybuf0))
    s_scr[...] = S
    y_ref[0, 0] = ybuf.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def wkv_bhtd(r, k, v, w, u, *, bt: int = 128, interpret: bool = False):
    """r,k,v,w [B,H,T,hd]; u [H,hd] -> y [B,H,T,hd]."""
    B, H, T, hd = r.shape
    bt = min(bt, T)
    nt = pl.cdiv(T, bt)

    kernel = functools.partial(_wkv_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nt),
        in_specs=[
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, 1, bt, hd), lambda b, h, it: (b, h, it, 0)),
            pl.BlockSpec((1, hd), lambda b, h, it: (h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bt, hd), lambda b, h, it: (b, h, it, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, T, hd), r.dtype),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u)
