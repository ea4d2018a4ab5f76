"""Expert-parallel MoE with explicit all-to-all dispatch (shard_map).

The ``dense`` MoE formulation computes every expert on every token
(E/top_k x wasted FLOPs — 16x on qwen3-moe); the ``gmm`` (ragged_dot)
formulation is exact but GSPMD cannot partition it over experts.  This
module is the production path: experts are sharded over the 'model' axis,
tokens are routed with a capacity-bounded dispatch and exchanged with
``lax.all_to_all`` — the direct analogue of the paper's transfer channels
(the a2a payload is "the dataset", expert capacity is the per-channel
window, and §Perf tunes the capacity factor exactly like the paper tunes
concurrency).

Token layout inside shard_map: [B/(pod·data), T/model, D] — both batch and
sequence sharded, so each device routes only its local tokens.

    x_send [E, C, D] --all_to_all--> [E_loc, mp*C, D] --experts-->
           [E_loc, mp*C, D] --all_to_all--> [E, C, D] --combine--> out
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig


def _axes():
    m = jax.sharding.get_abstract_mesh()
    names = m.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    return m, dp, ("model" if "model" in names else None)


def moe_a2a(cfg: ModelConfig, p, x, *, capacity_factor: float = 1.25):
    """Drop-in replacement for layers.moe_gmm/moe_dense under a mesh.

    x [B, T, D] -> (out [B, T, D], aux_loss scalar).
    """
    m, dp, model_ax = _axes()
    moe = cfg.moe
    assert moe is not None
    if model_ax is None or m.empty:
        from repro.models import layers as L
        return L.moe_gmm(cfg, p, x)

    mp = dict(m.shape)[model_ax]
    E, k = moe.num_experts, moe.top_k
    assert E % mp == 0, (E, mp)

    B, T, D = x.shape
    t_sharded = (T % mp == 0)
    x_spec = P(dp, model_ax if t_sharded else None, None)

    def body(xl, router, wg, wu, wd):
        Bl, Tl, _ = xl.shape
        N = Bl * Tl
        xf = xl.reshape(N, D)

        logits = xf.astype(jnp.float32) @ router          # [N, E]
        probs = jax.nn.softmax(logits, axis=-1)
        w, ids = lax.top_k(probs, k)                      # [N, k]
        w = w / jnp.sum(w, axis=-1, keepdims=True)

        # load-balance aux (local estimate)
        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(ids[:, 0], E, dtype=jnp.float32), axis=0)
        aux = E * jnp.sum(me * ce) * moe.load_balance_coef
        aux = lax.pmean(aux, dp + (model_ax,))

        # capacity-bounded dispatch
        C = max(int(math.ceil(N * k / E * capacity_factor)), 1)
        flat_e = ids.reshape(-1)                          # [N*k]
        oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(oh, axis=0) - 1,
                                  flat_e[:, None], axis=1)[:, 0]
        keep = pos < C
        slot = jnp.where(keep, pos, C)                    # overflow -> C
        tok = jnp.repeat(jnp.arange(N), k)

        send = jnp.zeros((E, C + 1, D), xl.dtype)
        send = send.at[flat_e, slot].set(xf[tok])         # dropped -> slot C
        send = send[:, :C]                                # [E, C, D]

        # dispatch a2a: [E, C, D] -> [E_loc, mp*C, D]
        recv = lax.all_to_all(send, model_ax, split_axis=0, concat_axis=1,
                              tiled=True)

        # local experts
        g = jnp.einsum("ecd,edf->ecf", recv, wg)
        u = jnp.einsum("ecd,edf->ecf", recv, wu)
        h = (jax.nn.silu(g) * u).astype(xl.dtype)
        y = jnp.einsum("ecf,efd->ecd", h, wd)             # [E_loc, mp*C, D]

        # return a2a: -> [E, C, D]
        back = lax.all_to_all(y, model_ax, split_axis=1, concat_axis=0,
                              tiled=True)

        # combine
        back_p = jnp.concatenate(
            [back, jnp.zeros((E, 1, D), back.dtype)], axis=1)
        gathered = back_p[flat_e, slot]                   # [N*k, D]
        wk = (w.reshape(-1) * keep.astype(jnp.float32)).astype(gathered.dtype)
        out = jnp.sum((gathered * wk[:, None]).reshape(N, k, D), axis=1)
        return out.reshape(Bl, Tl, D), aux

    specs_in = (x_spec, P(None, None), P(model_ax, None, None),
                P(model_ax, None, None), P(model_ax, None, None))
    out, aux = jax.shard_map(
        body, mesh=m, in_specs=specs_in,
        out_specs=(x_spec, P()), check_vma=False,
    )(x, p["router"], p["wg"], p["wu"], p["wd"])

    if moe.num_shared_experts:
        from repro.models import layers as L
        out = out + L.mlp(cfg, p["shared"], x.reshape(B * T, D)).reshape(
            B, T, D)
    return out, aux
