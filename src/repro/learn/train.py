"""Trainers for learned transfer controllers.

* :func:`bc_train` — behavior cloning: cross-entropy over (observation,
  teacher-action) pairs captured by the rollout harness, optimized with
  ``repro.optim.adamw``.  The whole loop is one ``lax.scan`` inside one
  jit, so a smoke-sized fit is sub-second after compile.
* :func:`pg_train` — REINFORCE on an energy·delay objective with a
  throughput-floor penalty: stochastic rollouts through the engine
  (Gumbel-max exploration), advantage-normalized returns, and a replayed
  log-probability pass that recovers each sampled action from the same
  (logits + noise) argmax the rollout executed.

Determinism: every entry point takes an explicit ``jax.random`` key —
:func:`seed_everything` makes the root key — and nothing else draws
randomness, so a (seed, data, config) triple reproduces parameters
bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import scenario as _scenario
from repro.core.types import SLA
from repro.optim import AdamWConfig
from repro.optim.adamw import adamw_init, adamw_update

from .controller import LearnedController
from .policy import PolicyConfig, apply_policy, featurize, init_policy
from .rollout import make_policy_rollout, n_ctrl_ticks


def seed_everything(seed: int):
    """One integer seed -> the root ``jax.random`` key every learn entry
    point derives from.  Also seeds numpy's legacy generator so any
    host-side shuffling downstream of the trainers is pinned too."""
    np.random.seed(seed & 0xFFFFFFFF)
    return jax.random.PRNGKey(seed)


def _default_opt(steps: int, lr: float) -> AdamWConfig:
    return AdamWConfig(lr=lr, weight_decay=1e-4, grad_clip=1.0,
                       warmup_steps=max(steps // 20, 1), total_steps=steps,
                       min_lr_frac=0.05)


def _cross_entropy(cfg, params, feats, labels):
    logits = apply_policy(cfg, params, feats)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()


def bc_train(feats, labels, *, key, cfg: PolicyConfig = PolicyConfig(),
             steps: int = 400, batch_size: int = 256,
             lr: float = 3e-3, opt: Optional[AdamWConfig] = None):
    """Fit the policy to teacher (features, action-class) pairs.

    Returns ``(params, history)`` with ``history["loss"]`` the per-step
    minibatch cross-entropy.  Bit-deterministic in (key, data, config).
    """
    feats = jnp.asarray(feats, jnp.float32)
    labels = jnp.asarray(labels, jnp.int32)
    n = feats.shape[0]
    batch = min(batch_size, n)
    opt = opt or _default_opt(steps, lr)
    k_init, k_train = jax.random.split(key)
    params0 = init_policy(cfg, k_init)

    def step_fn(carry, k):
        params, opt_state = carry
        idx = jax.random.randint(k, (batch,), 0, n)
        loss, grads = jax.value_and_grad(
            lambda p: _cross_entropy(cfg, p, feats[idx], labels[idx])
        )(params)
        params, opt_state, _ = adamw_update(opt, grads, opt_state, params)
        return (params, opt_state), loss

    @jax.jit
    def fit(params0, keys):
        (params, _), losses = jax.lax.scan(
            step_fn, (params0, adamw_init(params0)), keys)
        return params, losses

    params, losses = fit(params0, jax.random.split(k_train, steps))
    return (jax.tree.map(np.asarray, params),
            {"loss": np.asarray(losses)})


@dataclasses.dataclass(frozen=True)
class PGConfig:
    """REINFORCE hyper-parameters (objective: minimize energy·delay,
    penalized when average throughput falls below the floor)."""

    steps: int = 30
    lr: float = 1e-3
    tput_floor_mbps: float = 0.0
    floor_penalty: float = 5.0


def _prepare_lanes(scenarios: Sequence, controller: LearnedController):
    """Prepare scenarios as PG lanes (one shared engine code group)."""
    prepared = [_scenario._prepare(
        dataclasses.replace(sc, controller=controller))
        for sc in scenarios]
    merged = _scenario._merged_partition_counts([p.key for p in prepared])
    prepared = [_scenario._pad_partitions(p, merged[p.key])
                for p in prepared]
    keys = {p.key for p in prepared}
    if len(keys) != 1:
        raise ValueError(
            "PG lanes must share one engine code group (same cpu, horizon, "
            "dt, controller interval, partition count and whether a "
            f"bw_schedule is set); got {len(keys)}")
    stacked = jax.tree.map(lambda *xs: jnp.asarray(np.stack(xs)),
                           *[p.inputs for p in prepared])
    return prepared[0].key, stacked


def pg_train(scenarios: Sequence, *, key,
             cfg: PolicyConfig = PolicyConfig(),
             params=None, sla: SLA = SLA(),
             pg: PGConfig = PGConfig(),
             opt: Optional[AdamWConfig] = None):
    """REINFORCE over batched engine rollouts.

    ``scenarios`` are run as parallel lanes (their ``controller`` field is
    replaced by the in-training policy); ``params`` warm-starts from a BC
    fit when given.  Returns ``(params, history)`` where history tracks
    the mean energy·delay cost and penalty per update.
    """
    if params is None:
        key, k_init = jax.random.split(key)
        params = init_policy(cfg, k_init)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    controller = LearnedController(params=jax.tree.map(np.asarray, params),
                                   cfg=cfg, sla=sla)
    gkey, inputs = _prepare_lanes(scenarios, controller)
    n_steps, dt, ctrl_every = gkey.n_steps, gkey.dt, gkey.ctrl_every
    n_lanes = int(np.asarray(inputs.bw).shape[0])
    n_ctrl = n_ctrl_ticks(n_steps, ctrl_every)
    rollout = make_policy_rollout(cfg, gkey.env_code, gkey.cpu,
                                  n_steps=n_steps, dt=dt,
                                  ctrl_every=ctrl_every)
    opt = opt or _default_opt(pg.steps, pg.lr)
    net_b = jax.tree.map(lambda x: jnp.asarray(x)[:, None], inputs.net)
    sla_b = jax.tree.map(lambda x: jnp.asarray(x)[:, None], inputs.sla)

    def lane_cost(sim, metrics):
        finished = metrics.done[:, -1]
        t_done = jnp.where(
            finished,
            (jnp.argmax(metrics.done, axis=-1) + 1).astype(jnp.float32) * dt,
            n_steps * dt)
        tput = sim.bytes_moved / jnp.maximum(t_done, 1e-9)
        ed = sim.energy_j * t_done
        floor = pg.tput_floor_mbps
        pen = jnp.maximum(floor - tput, 0.0) / max(floor, 1e-9) \
            if floor > 0.0 else jnp.zeros_like(tput)
        return ed, pen

    sel = slice(ctrl_every - 1, n_steps, ctrl_every)

    def update(params, opt_state, ed_ref, k):
        noise = jax.random.gumbel(
            k, (n_lanes, n_ctrl, cfg.n_heads, cfg.n_classes), jnp.float32)
        sim, metrics, obs = rollout(jax.lax.stop_gradient(params), noise,
                                    inputs)
        ed, pen = lane_cost(sim, metrics)
        cost = ed / ed_ref + pg.floor_penalty * pen
        adv = (cost - cost.mean()) / (cost.std() + 1e-6)
        feats = featurize(obs.avg_tput[:, sel], obs.avg_power[:, sel],
                          obs.cpu_load[:, sel], obs.remaining_mb[:, sel],
                          obs.num_ch[:, sel], obs.cores[:, sel],
                          obs.freq_idx[:, sel], net=net_b, sla=sla_b,
                          cpu=gkey.cpu)
        mask = obs.is_ctrl[:, sel].astype(jnp.float32)
        noise_ct = noise[:, :feats.shape[1]]

        def loss_fn(p):
            logits = apply_policy(cfg, p, feats)
            cls = jnp.argmax(logits + noise_ct, axis=-1)
            logp = jax.nn.log_softmax(logits, axis=-1)
            taken = jnp.take_along_axis(
                logp, cls[..., None], axis=-1)[..., 0].sum(axis=-1)
            lane_logp = (taken * mask).sum(axis=-1)
            return (adv * lane_logp).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state, _ = adamw_update(opt, grads, opt_state, params)
        stats = jnp.stack([loss, cost.mean(), ed.mean(), pen.mean()])
        return params, opt_state, stats

    update = jax.jit(update)

    # Reference energy·delay from a greedy pass with the starting params:
    # normalizes the return scale so lr/penalty are workload-independent.
    zeros = jnp.zeros((n_lanes, n_ctrl, cfg.n_heads, cfg.n_classes),
                      jnp.float32)
    sim0, metrics0, _ = jax.jit(rollout)(params, zeros, inputs)
    ed0, _ = lane_cost(sim0, metrics0)
    ed_ref = jnp.maximum(jnp.mean(ed0), 1e-6)

    history = []
    opt_state = adamw_init(params)
    for k in jax.random.split(key, pg.steps):
        params, opt_state, stats = update(params, opt_state, ed_ref, k)
        history.append(np.asarray(stats))
    hist = np.stack(history) if history else np.zeros((0, 4))
    return (jax.tree.map(np.asarray, params),
            {"loss": hist[:, 0], "cost": hist[:, 1], "energy_delay":
             hist[:, 2], "floor_penalty": hist[:, 3],
             "ed_ref": float(ed_ref)})
