"""Rollout harness: drive the ``jit(vmap(scan))`` engine as a batched
environment.

Two modes:

* **Teacher capture** (:func:`run_observed`, :func:`teacher_dataset`) —
  run any scenarios through the cached engine runners with the
  ``observe=True`` hook and harvest per-tick ``Observation`` traces:
  window throughput/power, operating point, contention share, and the
  action deltas the controller applied.  Controller ticks become
  (features, action-class) pairs — the behavior-cloning dataset.

* **Policy rollout** (:func:`make_policy_rollout`) — a vmapped engine
  core whose controller closes over *traced* policy params, so a
  policy-gradient loop re-rolls thousands of lanes per update without
  recompiling.  Exploration is Gumbel-max sampling from pre-drawn noise:
  the tuner state's ``fsm`` slot counts controller ticks and indexes the
  lane's noise table, which makes the sampled action a deterministic
  function of (params, noise) — the PG loss replays the exact same argmax
  to recover the sampled class and its log-probability.
"""
from __future__ import annotations

from collections import defaultdict
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import scenario as _scenario
from repro.core import engine, heuristics

from .policy import (PolicyConfig, action_classes, apply_action,
                     apply_policy, featurize)


class ObservedRun(NamedTuple):
    """One scenario's observed rollout (numpy leaves)."""

    prep: _scenario._Prepared
    sim: object            # final SimState
    metrics: object        # TickMetrics [n_steps]
    obs: engine.Observation    # [n_steps]


def run_observed(scenarios: Sequence) -> list[ObservedRun]:
    """Run scenarios through the engine with the observation hook on.

    Mirrors ``repro.api.sweep``'s grouping (pad partitions, stack, one
    vmapped launch per code group) so a whole teacher grid is a handful of
    XLA calls; results come back in input order.
    """
    prepared = [_scenario._prepare(sc) for sc in scenarios]
    merged = _scenario._merged_partition_counts([p.key for p in prepared])
    prepared = [_scenario._pad_partitions(p, merged[p.key])
                for p in prepared]
    groups: dict = defaultdict(list)
    for i, prep in enumerate(prepared):
        groups[prep.key].append(i)

    results: list = [None] * len(prepared)
    for key, idxs in groups.items():
        # An explicit Scenario.executor="pallas" raises in get_runner: the
        # fused kernel has no observation outputs.
        if len(idxs) == 1:
            runner = engine.get_runner(
                key.ctrl_code, key.env_code, key.cpu, key.n_steps, key.dt,
                key.ctrl_every, batched=False, observe=True,
                executor=key.executor)
            out = runner(prepared[idxs[0]].inputs)
            batch = [(idxs[0], out)]
        else:
            stacked = jax.tree.map(lambda *xs: np.stack(xs),
                                   *[prepared[i].inputs for i in idxs])
            runner = engine.get_runner(
                key.ctrl_code, key.env_code, key.cpu, key.n_steps, key.dt,
                key.ctrl_every, batched=True, observe=True,
                executor=key.executor)
            sim, ts, metrics, obs = runner(stacked)
            batch = [(i, jax.tree.map(lambda x, b=b: x[b],
                                      (sim, ts, metrics, obs)))
                     for b, i in enumerate(idxs)]
        for i, (sim, _, metrics, obs) in batch:
            results[i] = ObservedRun(
                prep=prepared[i],
                sim=jax.tree.map(np.asarray, sim),
                metrics=jax.tree.map(np.asarray, metrics),
                obs=jax.tree.map(np.asarray, obs))
    return results


def teacher_dataset(scenarios: Sequence,
                    *, max_samples: int | None = None):
    """Behavior-cloning dataset from heuristic-controller rollouts.

    Returns ``(feats [N, F] float32, labels [N, n_heads] int32)`` — one row
    per live controller tick, features computed with the same
    :func:`repro.learn.policy.featurize` the learned controller runs at
    inference.  ``max_samples`` truncates deterministically (front-first).
    """
    feats_out, labels_out = [], []
    for run in run_observed(scenarios):
        obs = run.obs
        mask = np.asarray(obs.is_ctrl, bool)
        if not mask.any():
            continue
        net = run.prep.inputs.net
        sla = run.prep.inputs.sla
        feats = featurize(obs.avg_tput, obs.avg_power, obs.cpu_load,
                          obs.remaining_mb, obs.num_ch, obs.cores,
                          obs.freq_idx, net=net, sla=sla,
                          cpu=run.prep.key.cpu)
        labels = action_classes(obs.d_num_ch, obs.d_cores, obs.d_freq_idx)
        feats_out.append(np.asarray(feats)[mask])
        labels_out.append(np.asarray(labels)[mask])
    if not feats_out:
        raise ValueError("no controller ticks observed — do the scenarios "
                         "use a tuning controller and a horizon >= one "
                         "controller interval?")
    feats = np.concatenate(feats_out).astype(np.float32)
    labels = np.concatenate(labels_out).astype(np.int32)
    if max_samples is not None:
        feats, labels = feats[:max_samples], labels[:max_samples]
    return feats, labels


class _SampledPolicy:
    """Policy controller over *traced* params with Gumbel-max exploration.

    Used only inside the jitted PG rollout (never hashed or cached): the
    params and the per-lane noise table are tracers closed over by the
    scan step.  ``state.fsm`` counts controller ticks (the engine gates
    ticks on liveness, so the counter is dense from 0) and selects the
    tick's noise row.
    """

    tunes = True
    name = "learned-sample"

    def __init__(self, cfg: PolicyConfig, params, noise):
        self.cfg = cfg
        self.params = params
        self.noise = noise          # [n_ctrl, n_heads, n_classes]

    def tick(self, state, meas, net, cpu, sla):
        feats = featurize(meas.avg_tput, meas.avg_power, meas.cpu_load,
                          meas.remaining_mb, state.num_ch, state.cores,
                          state.freq_idx, net=net, sla=sla, cpu=cpu)
        logits = apply_policy(self.cfg, self.params, feats)
        k = jnp.minimum(state.fsm, self.noise.shape[0] - 1)
        gumbel = jax.lax.dynamic_index_in_dim(self.noise, k, axis=0,
                                              keepdims=False)
        cls = jnp.argmax(logits + gumbel, axis=-1)
        num_ch, cores, freq_idx = apply_action(
            state.num_ch, state.cores, state.freq_idx, cls, sla=sla,
            cpu=cpu)
        return state._replace(num_ch=num_ch, prev_num_ch=state.num_ch,
                              cores=cores, freq_idx=freq_idx,
                              fsm=state.fsm + 1)

    def channels(self, state, sim, static_w):
        return heuristics.redistribute_channels(state.num_ch,
                                                sim.remaining_mb)


def n_ctrl_ticks(n_steps: int, ctrl_every: int) -> int:
    """Controller ticks in a full horizon (ticks fire at step indices
    ``ctrl_every - 1, 2*ctrl_every - 1, ...``)."""
    return max(n_steps // ctrl_every, 1)


def make_policy_rollout(cfg: PolicyConfig, env, cpu, *, n_steps: int,
                        dt: float, ctrl_every: int):
    """Batched full-horizon rollout ``(params, noise, inputs) -> (sim,
    metrics, obs)`` with the policy sampling via Gumbel noise.

    ``noise`` is ``[lanes, n_ctrl_ticks, n_heads, n_classes]``; pass zeros
    for a greedy (argmax) rollout.  Not jitted here — PG updates jit the
    rollout together with the loss so one compile covers the whole step.
    """

    def single(params, noise, inp):
        ctrl = _SampledPolicy(cfg, params, noise)
        sim0 = env.network.init_state(inp.total_mb, inp.net)
        step = engine.make_step_fn(ctrl, env, cpu, inp, dt=dt,
                                   ctrl_every=ctrl_every, observe=True)
        xs = (jnp.arange(n_steps, dtype=jnp.int32),
              engine.tick_bw(inp.bw, n_steps)(0, n_steps))
        (sim, ts), (metrics, obs) = jax.lax.scan(step, (sim0, inp.state0),
                                                 xs)
        return sim, metrics, obs

    return jax.vmap(single, in_axes=(None, 0, 0))
