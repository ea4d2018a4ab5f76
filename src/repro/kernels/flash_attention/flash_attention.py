"""Flash attention forward — Pallas TPU kernel.

Schedule: grid (B, H, nQ, nK) with the K axis innermost ("arbitrary" =
sequential on TPU), carrying the online-softmax state (m, l, acc) in VMEM
scratch across K steps.  Q/K/V blocks are tiled into VMEM via BlockSpec;
the MXU sees [bq, hd] x [hd, bk] and [bq, bk] x [bk, hd] matmuls with
hardware-aligned dims (bq = bk = 128, hd in {64, 128, 256}).

GQA is handled by the BlockSpec index_map (query head h reads kv head
h // group) — no repeated K/V materialization in HBM.

Supports causal masking and sliding-window (local) attention; fully-masked
K blocks are skipped via pl.when, so the causal schedule does ~half the
work and a local-attention schedule touches only O(window) K blocks.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *rest, bq: int, bk: int, nk: int,
               causal: bool, window: int, scale: float,
               with_lse: bool = False):
    if with_lse:
        lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        m_scr, l_scr, acc_scr = rest
        lse_ref = None
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    q_start = iq * bq
    k_start = ik * bk

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Block-level reachability: causal -> skip blocks entirely above the
    # diagonal; windowed -> skip blocks entirely left of the window.
    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window > 0:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)            # [bk, hd]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        if causal or window > 0:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.zeros((bq, bk), jnp.bool_)
            if causal:
                mask |= kpos > qpos
            if window > 0:
                mask |= kpos <= qpos - window
            s = jnp.where(mask, NEG_INF, s)

        m_prev = m_scr[...]                              # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                           # [bq, bk]
        corr = jnp.exp(m_prev - m_new)                   # [bq, 1]
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0].astype(
                lse_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "bq", "bk", "interpret",
                     "return_lse"))
def flash_attention_bhtd(q, k, v, *, causal: bool = True, window: int = 0,
                         bq: int = 128, bk: int = 128,
                         interpret: bool = False, return_lse: bool = False):
    """q [B,H,Tq,hd], k/v [B,Hkv,Tk,hd] -> o [B,H,Tq,hd] (+ lse [B,H,Tq]
    when ``return_lse`` — consumed by the backward kernels)."""
    B, H, Tq, hd = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    nq = pl.cdiv(Tq, bq)
    nk = pl.cdiv(Tk, bk)
    scale = 1.0 / math.sqrt(hd)

    kernel = functools.partial(_fa_kernel, bq=bq, bk=bk, nk=nk,
                               causal=causal, window=window, scale=scale,
                               with_lse=return_lse)

    o_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0))
    out_specs, out_shape = o_spec, jax.ShapeDtypeStruct((B, H, Tq, hd),
                                                        q.dtype)
    if return_lse:
        lse_spec = pl.BlockSpec((1, 1, bq), lambda b, h, iq, ik: (b, h, iq))
        out_specs = (o_spec, lse_spec)
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((B, H, Tq), jnp.float32))

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # running denominator
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
