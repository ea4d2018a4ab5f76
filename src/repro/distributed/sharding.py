"""Partition rules + batch-sharding helpers.

Two jobs live here:

1. Parameter partitioning for the model stack: map every parameter path to a
   PartitionSpec (the bulk of this module).
2. Scenario-batch sharding for the transfer engine: a 1-D ``batch`` mesh over
   the local devices plus pad/place helpers, used by ``repro.api.sweep`` to
   run one vmapped engine group as per-device shards (see
   ``repro.core.engine.get_sharded_runner``).

Mesh axes:
    single pod:  (data=16, model=16)
    multi-pod:   (pod=2, data=16, model=16)  — batch shards over (pod, data),
                 gradients all-reduce across pods on the same spec.

Tensor-parallel scheme (megatron-style):
    embed   [V, D]          -> (model, None)    vocab-sharded; logits RS/AG
    wq/wk/wv [D, H*hd]      -> (None, model)    head-sharded (column)
    wo      [H*hd, D]       -> (model, None)    row
    mlp wg/wu [D, F]        -> (None, model)    column
    mlp wd  [F, D]          -> (model, None)    row
    MoE experts [E, D, F]   -> (model, None, None)  expert-parallel
    rwkv time-mix projs     -> column/row like attention
    rglru wx/wy|wo          -> column/row; gate block-diagonals replicated
    1-D params (norms, mus) -> replicated

Stacked-layer params carry a leading L axis -> prepend None.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Global device-mesh configuration (Alpa-style options surface).

    Describes a fleet's execution substrate as ``num_hosts`` processes of
    ``devices_per_host`` accelerators each, flattened into a single 1-D
    ``batch`` mesh for the slot-pool wave runners.  The fleet loop
    (``repro.fleet.online``) treats the config as the *logical* mesh:
    admission and slot assignment run on host 0 (deterministic — every
    lane's slot index is a pure function of the arrival stream, so all
    hosts agree on the broadcast layout), and a sharded wave's width is a
    multiple of the mesh size so ``shard_batch`` placements divide evenly.

    ``None`` fields auto-detect: one host, all local devices.  ``.devices()``
    validates the request against what the runtime actually exposes —
    asking for an 8-device mesh in a 1-device process raises rather than
    silently running unsharded (force CPU device counts in tests with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """

    num_hosts: int = 1
    devices_per_host: Optional[int] = None

    def __post_init__(self):
        if self.num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, got {self.num_hosts}")
        if self.devices_per_host is not None and self.devices_per_host < 1:
            raise ValueError(f"devices_per_host must be >= 1, got "
                             f"{self.devices_per_host}")

    @property
    def mesh_size(self) -> Optional[int]:
        if self.devices_per_host is None:
            return None
        return self.num_hosts * self.devices_per_host

    def devices(self) -> tuple:
        """The flattened (hosts x devices_per_host) device tuple, validated
        against the runtime's visible devices."""
        avail = tuple(jax.devices())
        want = self.mesh_size
        if want is None:
            return avail
        if want > len(avail):
            raise ValueError(
                f"MeshConfig wants {self.num_hosts} hosts x "
                f"{self.devices_per_host} devices = {want}, but only "
                f"{len(avail)} devices are visible")
        return avail[:want]

    def mesh(self) -> Mesh:
        """1-D ``batch`` mesh over :meth:`devices`."""
        return batch_mesh(self.devices())


def batch_mesh(devices=None) -> Mesh:
    """1-D mesh with a single ``batch`` axis over ``devices``.

    ``devices`` defaults to all local devices; pass an explicit tuple to pin
    a sweep to a subset (the tuple also serves as the runner cache key — see
    ``repro.core.engine.get_sharded_runner``).
    """
    devices = tuple(jax.devices() if devices is None else devices)
    return Mesh(np.asarray(devices), ("batch",))


def should_shard(lanes: int, devices) -> bool:
    """Whether a batch of ``lanes`` engine lanes runs sharded over
    ``devices`` (``repro.api.sweep`` groups and the fleet loop's waves
    alike; a fleet wave counts its occupied slots).

    Only when every device gets at least one lane: a smaller batch would
    pay padding lanes plus an extra compiled executable for no wall-clock
    win over the plain vmapped runner on one device.  A lane's results do
    not depend on the path, since every per-tick partition sum has a fixed
    order at any batch width (``repro.core.types.partition_sum``).
    """
    return devices is not None and 1 < len(devices) <= lanes


def pad_batch(tree, multiple: int, *, fill: str = "repeat"):
    """Pad axis 0 of every leaf up to a multiple of ``multiple``.

    Returns ``(padded_tree, original_batch_size)``; callers slice results
    back to the original size.  ``fill`` selects the padding rows:

    * ``"repeat"`` (default) repeats the last row — numerically
      well-behaved for sweep groups, where a padding lane simulates a
      duplicate scenario and the group's early-exit loop waits for it to
      finish like any other lane.
    * ``"zero"`` appends zero rows: a zeroed engine lane has no bytes
      remaining, so it is born drained and frozen from tick 0.  The fleet
      loop needs no padding (its slot pools hold the widest wave's rows,
      zeroed where free), so no caller in the package uses it.
    """
    if fill not in ("repeat", "zero"):
        raise ValueError(f"unknown fill mode {fill!r}")
    sizes = {np.shape(leaf)[0] for leaf in jax.tree.leaves(tree)}
    if len(sizes) != 1:
        raise ValueError(f"inconsistent batch sizes in pytree: {sizes}")
    b = sizes.pop()
    pad = (-b) % multiple
    if pad == 0:
        return tree, b
    if fill == "zero":
        return jax.tree.map(
            lambda x: np.concatenate(
                [x, np.zeros((pad,) + np.shape(x)[1:], np.asarray(x).dtype)]),
            tree), b
    return jax.tree.map(
        lambda x: np.concatenate([x, np.repeat(x[-1:], pad, axis=0)]),
        tree), b


def shard_batch(tree, mesh: Mesh):
    """Place a stacked (batch-leading) pytree on ``mesh`` sharded along
    ``batch``.  Axis 0 of every leaf must divide the mesh size — pad with
    :func:`pad_batch` first."""
    return jax.device_put(tree, NamedSharding(mesh, P("batch")))


# (regex on '/'-joined path, spec WITHOUT the stacked-layer axis)
_RULES = (
    (r"embed$",                      P("model", None)),
    (r"head$",                       P(None, "model")),
    (r"(attn|self_attn|cross_attn)/w[qkv]$", P(None, "model")),
    (r"(attn|self_attn|cross_attn)/wo$",     P("model", None)),
    (r"(attn|self_attn|cross_attn)/b[qkv]$", P("model")),
    # moe experts: expert-parallel over the model axis
    (r"moe/w[gu]$",                  P("model", None, None)),
    (r"moe/wd$",                     P("model", None, None)),
    (r"moe/router$",                 P(None, None)),
    (r"moe/shared/w[gu]$",           P(None, "model")),
    (r"moe/shared/wd$",              P("model", None)),
    # dense mlp
    (r"mlp/w[gu]$",                  P(None, "model")),
    (r"mlp/wd$",                     P("model", None)),
    (r"mlp/b[ud]$",                  P(None)),
    # rwkv time-mix / channel-mix
    (r"tm/w[rkvg]$",                 P(None, "model")),
    (r"tm/wo$",                      P("model", None)),
    (r"tm/(mix_A|mix_B|w_A|w_B|mu|w0|u|gn_scale)$", None),  # small, replicated
    (r"cm/w[k]$",                    P(None, "model")),
    (r"cm/wv$",                      P("model", None)),
    (r"cm/wr$",                      P(None, "model")),
    (r"cm/(mu_k|mu_r)$",             None),
    # rglru recurrent blocks
    (r"rec/w[xy]$",                  P(None, "model")),
    (r"rec/wo$",                     P("model", None)),
    (r"rec/conv_[wb]$",              None),
    (r"rec/(gate_a|gate_x)/[wb]$",   None),
    (r"rec/lam$",                    None),
)


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def spec_for(path_str: str, ndim: int, stacked: bool,
             shape=None, model_divisor: int = 16) -> P:
    for pat, spec in _RULES:
        if re.search(pat, path_str):
            if spec is None:
                return P()
            want = len(spec) + (1 if stacked else 0)
            if ndim == want and stacked:
                spec = P(None, *spec)
            elif ndim != len(spec):
                # dimensionality mismatch (e.g. layer-stacked bias): replicate
                return P()
            if shape is not None:
                # drop 'model' from dims the axis size does not divide
                # (e.g. whisper's vocab 51865) instead of forcing GSPMD
                # padding.
                fixed = tuple(
                    None if (ax == "model" and dim % model_divisor != 0)
                    else ax
                    for ax, dim in zip(tuple(spec), shape))
                spec = P(*fixed)
            return spec
    return P()   # default: replicated (norms, scalars)


def param_specs(params, *, stacked_blocks_key: str = "blocks",
                model_divisor: int = 16):
    """PartitionSpec pytree matching ``params``; layer-stacked subtrees
    (under ``blocks``) get a leading None axis."""

    def per_leaf(path, leaf):
        ps = _path_str(path)
        stacked = ps.startswith(stacked_blocks_key + "/") or \
            ("/" + stacked_blocks_key + "/") in ps
        return spec_for(ps, leaf.ndim, stacked, shape=leaf.shape,
                        model_divisor=model_divisor)

    return jax.tree_util.tree_map_with_path(per_leaf, params)


def data_axes(mesh: Mesh):
    names = mesh.axis_names
    return ("pod", "data") if "pod" in names else ("data",)


def batch_spec(mesh: Mesh) -> P:
    return P(data_axes(mesh), None)


def shardings(mesh: Mesh, specs):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_specs(param_spec_tree, opt_state):
    """AdamW mu/nu shard exactly like their parameters."""
    from repro.optim import OptState
    return OptState(mu=param_spec_tree, nu=param_spec_tree,
                    count=P())


def zero_specs(pspecs, params_shapes, mesh: Mesh):
    """ZeRO-style widening: additionally shard the first replicated,
    divisible dim of every param over the 'data' axis.  Used for the fp32
    optimizer moments and the microbatch gradient accumulator — at 30B-MoE
    scale those dominate per-device memory (measured 19 GB/device without)."""
    dsz = mesh.shape.get("data", 1)
    if dsz <= 1:
        return pspecs

    def widen(spec, leaf):
        s = list(tuple(spec) + (None,) * (leaf.ndim - len(spec)))
        for i, (ax, dim) in enumerate(zip(s, leaf.shape)):
            if ax is None and dim % dsz == 0:
                s[i] = "data"
                return P(*s)
        return P(*s)

    return jax.tree.map(widen, pspecs, params_shapes,
                        is_leaf=lambda x: isinstance(x, P))
