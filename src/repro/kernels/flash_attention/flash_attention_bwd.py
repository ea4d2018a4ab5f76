"""Flash attention backward — Pallas TPU kernels.

Standard two-kernel schedule with the forward's log-sum-exp (LSE) saved:

  dq kernel:   grid (B, H, nQ, nK)  — K innermost, dq accumulated in VMEM
  dkdv kernel: grid (B, H, nK, nQ)  — Q innermost, dk/dv accumulated in VMEM

With  p = exp(q·kᵀ·s − lse),  delta = rowsum(dO ∘ O):
  ds = p ∘ (dO·vᵀ − delta)·s
  dq = ds·k        dk = dsᵀ·q        dv = pᵀ·dO

GQA: both kernels run per *query* head (kv head h//rep via index_map); the
wrapper group-sums dk/dv over the rep axis.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _mask(s, q_start, k_start, bq, bk, causal, window):
    if not (causal or window > 0):
        return s
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    m = jnp.zeros((bq, bk), jnp.bool_)
    if causal:
        m |= kpos > qpos
    if window > 0:
        m |= kpos <= qpos - window
    return jnp.where(m, NEG_INF, s)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, bq, bk, nk, causal, window, scale):
    iq, ik = pl.program_id(2), pl.program_id(3)
    q_start, k_start = iq * bq, ik * bk

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        run = k_start <= q_start + bq - 1
    if window > 0:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)[:, None]      # [bq,1]
        delta = delta_ref[0, 0].astype(jnp.float32)[:, None]  # [bq,1]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask(s, q_start, k_start, bq, bk, causal, window)
        p = jnp.exp(s - lse)                                   # [bq,bk]
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (dov - delta) * scale
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _fin():
        dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                 dk_ref, dv_ref, dk_scr, dv_scr, *, bq, bk, nq, causal,
                 window, scale):
    ik, iq = pl.program_id(2), pl.program_id(3)
    q_start, k_start = iq * bq, ik * bk

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = q_start + bq - 1 >= k_start
    if window > 0:
        run = jnp.logical_and(run, q_start <= k_start + bk - 1 + window)

    @pl.when(run)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0].astype(jnp.float32)[:, None]
        delta = delta_ref[0, 0].astype(jnp.float32)[:, None]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = _mask(s, q_start, k_start, bq, bk, causal, window)
        p = jnp.exp(s - lse)                                   # [bq,bk]
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk,hd]
        dov = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ds = p * (dov - delta) * scale
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk,hd]

    @pl.when(iq == nq - 1)
    def _fin():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                              "interpret"))
def flash_attention_bwd_bhtd(q, k, v, o, lse, do, *, causal=True, window=0,
                             bq=128, bk=128, interpret=False):
    """Inputs [B,H,Tq,hd] (k/v [B,Hkv,Tk,hd]); lse [B,H,Tq].

    Returns (dq [B,H,Tq,hd], dk/dv [B,Hkv,Tk,hd])."""
    B, H, Tq, hd = q.shape
    Hkv, Tk = k.shape[1], k.shape[2]
    group = H // Hkv
    bq = min(bq, Tq)
    bk = min(bk, Tk)
    nq, nk = pl.cdiv(Tq, bq), pl.cdiv(Tk, bk)
    scale = 1.0 / math.sqrt(hd)

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    q_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, iq, ik: (b, h, iq, 0))
    kq_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda b, h, iq, ik: (b, h // group, ik, 0))
    r_spec = pl.BlockSpec((1, 1, bq), lambda b, h, iq, ik: (b, h, iq))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk, nk=nk, causal=causal,
                          window=window, scale=scale),
        grid=(B, H, nq, nk),
        in_specs=[q_spec, kq_spec, kq_spec, q_spec, r_spec, r_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv per query head, then group-sum to kv heads.
    qk_spec = pl.BlockSpec((1, 1, bq, hd), lambda b, h, ik, iq: (b, h, iq, 0))
    kk_spec = pl.BlockSpec((1, 1, bk, hd),
                           lambda b, h, ik, iq: (b, h // group, ik, 0))
    ok_spec = pl.BlockSpec((1, 1, bk, hd), lambda b, h, ik, iq: (b, h, ik, 0))
    rk_spec = pl.BlockSpec((1, 1, bq), lambda b, h, ik, iq: (b, h, iq))

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkdv_kernel, bq=bq, bk=bk, nq=nq, causal=causal,
                          window=window, scale=scale),
        grid=(B, H, nk, nq),
        in_specs=[qk_spec, kk_spec, kk_spec, qk_spec, rk_spec, rk_spec],
        out_specs=(ok_spec, ok_spec),
        out_shape=(jax.ShapeDtypeStruct((B, H, Tk, hd), q.dtype),
                   jax.ShapeDtypeStruct((B, H, Tk, hd), q.dtype)),
        scratch_shapes=[pltpu.VMEM((bk, hd), jnp.float32),
                        pltpu.VMEM((bk, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    dk = dk_h.reshape(B, Hkv, group, Tk, hd).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, group, Tk, hd).sum(axis=2).astype(v.dtype)
    return dq, dk, dv
