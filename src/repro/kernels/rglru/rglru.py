"""RG-LRU gated linear recurrence — Pallas TPU kernel.

    h_t = a_t ⊙ h_{t-1} + b_t        (a_t, b_t precomputed by the caller:
                                      a_t = exp(c·r_t·logσΛ),
                                      b_t = sqrt(1−a_t²)·(i_t ⊙ x_t))

Grid (B, nC, nT): channels are "parallel" (each channel block independent),
time is innermost/sequential with the carry h [1, bc] in fp32 VMEM scratch.
Channel blocking (bc = 512, lane-aligned) keeps the working set
[bt, bc] x 3 well inside VMEM while giving the VPU full 8x128 vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, h_ref, carry_scr, *, bt: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    a = a_ref[0].astype(jnp.float32)         # [bt, bc]
    b = b_ref[0].astype(jnp.float32)

    def step(t, carry):
        h, ybuf = carry
        at = lax.dynamic_slice_in_dim(a, t, 1, 0)   # [1, bc]
        bt_ = lax.dynamic_slice_in_dim(b, t, 1, 0)
        h = at * h + bt_
        ybuf = lax.dynamic_update_slice_in_dim(ybuf, h, t, 0)
        return h, ybuf

    h0 = carry_scr[...]
    ybuf0 = jnp.zeros_like(a)
    h, ybuf = lax.fori_loop(0, bt, step, (h0, ybuf0))
    carry_scr[...] = h
    h_ref[0] = ybuf.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "bc", "interpret"))
def rglru_scan(a, b, *, bt: int = 256, bc: int = 512,
               interpret: bool = False):
    """a, b [B, T, C] -> h [B, T, C] with h_t = a_t*h_{t-1} + b_t."""
    B, T, C = a.shape
    bt = min(bt, T)
    bc = min(bc, C)
    nt = pl.cdiv(T, bt)
    nc = pl.cdiv(C, bc)

    kernel = functools.partial(_rglru_kernel, bt=bt)
    return pl.pallas_call(
        kernel,
        grid=(B, nc, nt),
        in_specs=[
            pl.BlockSpec((1, bt, bc), lambda bb, ic, it: (bb, it, ic)),
            pl.BlockSpec((1, bt, bc), lambda bb, ic, it: (bb, it, ic)),
        ],
        out_specs=pl.BlockSpec((1, bt, bc), lambda bb, ic, it: (bb, it, ic)),
        out_shape=jax.ShapeDtypeStruct((B, T, C), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
