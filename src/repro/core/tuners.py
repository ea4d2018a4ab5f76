"""The three SLA tuning algorithms (paper §IV, Algorithms 4-6) + Slow Start.

Each tuner is a *pure, jit-safe* function

    update(ts: TunerState, meas: Measurement, ...) -> TunerState

so the whole controller runs inside the engine's ``lax.scan`` (and can be
``vmap``-ed across parameter sweeps).  Branching over FSM states is done with
scalar ``jnp.where`` chains — every branch is a handful of scalar flops, so
computing all of them is cheaper than a ``lax.switch``.

The same objects drive the real host-side data pipeline (repro.data), where
``Measurement`` comes from wall-clock byte counters instead of the simulator.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from . import fsm
from .load_control import load_control
from .types import CpuProfile, NetworkProfile, SLA, SLAPolicy, TunerState


class Measurement(NamedTuple):
    """Observables accumulated over one controller interval ("Timeout")."""

    avg_tput: jnp.ndarray      # MB/s over the interval
    energy_j: jnp.ndarray      # J consumed during the interval (E_last)
    avg_power: jnp.ndarray     # W over the interval
    remaining_mb: jnp.ndarray  # total bytes left
    cpu_load: jnp.ndarray      # fraction [0,1]
    interval_s: jnp.ndarray


def init_tuner_state(num_ch0, cores0, freq_idx0) -> TunerState:
    """The Slow-Start state as host (numpy) 0-d arrays: building it touches
    no device, and the updates below take numpy leaves as they are."""
    return TunerState(
        fsm=np.asarray(fsm.SLOW_START, np.int32),
        num_ch=np.asarray(num_ch0, np.float32),
        prev_num_ch=np.asarray(num_ch0, np.float32),
        ref=np.zeros((), np.float32),
        cores=np.asarray(cores0, np.int32),
        freq_idx=np.asarray(freq_idx0, np.int32),
        acc_mb=np.zeros((), np.float32),
        acc_j=np.zeros((), np.float32),
        acc_s=np.zeros((), np.float32),
    )


def _me_metric(meas: Measurement):
    """E_last + E_future  (Algorithm 4 lines 5-6)."""
    remain_time = meas.remaining_mb / jnp.maximum(meas.avg_tput, 1e-3)
    e_future = meas.avg_power * remain_time
    return meas.energy_j + e_future


def slow_start(ts: TunerState, meas: Measurement, profile: NetworkProfile,
               sla, policy: SLAPolicy = None) -> TunerState:
    """Algorithm 2 — one corrective step after the first timeout.

    numCh *= bandwidth / lastThroughput, then hand over to INCREASE with the
    reference metric primed from this first measurement.

    ``sla`` may be a static :class:`SLA` or a traceable
    :class:`~repro.core.types.SLAParams`; in the latter case ``policy`` must
    be passed explicitly (it selects code, so it cannot be traced).
    """
    policy = sla.policy if policy is None else policy
    goal = profile.bandwidth_mbps
    if policy == SLAPolicy.TARGET_THROUGHPUT:
        tgt = sla.target_tput_mbps
        goal = jnp.where(tgt > 0.0, jnp.minimum(goal, tgt), goal)
    corr = goal / jnp.maximum(meas.avg_tput, 1e-3)
    corr = jnp.clip(corr, 0.25, 8.0)   # don't let a cold window explode numCh
    num_ch = jnp.clip(ts.num_ch * corr, 1.0, sla.max_ch * 1.0)
    ref = jnp.where(
        jnp.asarray(policy == SLAPolicy.MIN_ENERGY),
        _me_metric(meas),
        meas.avg_tput,
    )
    return ts._replace(fsm=jnp.asarray(fsm.INCREASE, jnp.int32),
                       num_ch=num_ch, prev_num_ch=ts.num_ch, ref=ref)


def me_update(ts: TunerState, meas: Measurement, sla: SLA) -> TunerState:
    """Algorithm 4 — Minimum energy. Feedback metric: E_last + E_future."""
    m = _me_metric(meas)
    a, b, d, mx = sla.alpha, sla.beta, sla.delta_ch * 1.0, sla.max_ch * 1.0
    st, ch, ref = ts.fsm, ts.num_ch, ts.ref

    improved = m < (1.0 - a) * ref
    degraded = m > (1.0 + b) * ref
    ok = jnp.logical_not(degraded)             # m <= (1+β)·E_past

    # INCREASE (lines 7-12)
    ch_inc = jnp.where(improved, jnp.minimum(ch + d, mx), ch)
    st_inc = jnp.where(degraded, fsm.WARNING, fsm.INCREASE)
    ref_inc = m                                 # reference tracks last estimate

    # WARNING (lines 13-19)
    ch_warn = jnp.where(ok, ch, jnp.maximum(ch - d, 1.0))
    st_warn = jnp.where(ok, fsm.INCREASE, fsm.RECOVERY)

    # RECOVERY (lines 20-26): keep reduction if it helped, else restore.
    ch_rec = jnp.where(ok, ch, jnp.minimum(ch + d, mx))
    st_rec = jnp.asarray(fsm.INCREASE)
    ref_rec = jnp.where(ok, ref, m)             # bandwidth changed -> rebase

    in_inc = st == fsm.INCREASE
    in_warn = st == fsm.WARNING
    new_ch = jnp.where(in_inc, ch_inc, jnp.where(in_warn, ch_warn, ch_rec))
    new_st = jnp.where(in_inc, st_inc, jnp.where(in_warn, st_warn, st_rec))
    new_ref = jnp.where(in_inc, ref_inc, jnp.where(in_warn, ref, ref_rec))

    return ts._replace(fsm=new_st.astype(jnp.int32), num_ch=new_ch,
                       prev_num_ch=ch, ref=new_ref)


def eemt_update(ts: TunerState, meas: Measurement, sla: SLA) -> TunerState:
    """Algorithm 5 — Energy-efficient maximum throughput."""
    tput = meas.avg_tput
    a, b, d, mx = sla.alpha, sla.beta, sla.delta_ch * 1.0, sla.max_ch * 1.0
    st, ch, ref = ts.fsm, ts.num_ch, ts.ref

    better = tput > (1.0 + b) * ref
    worse = tput < (1.0 - a) * ref
    ok = jnp.logical_not(worse)                 # tput >= (1−α)·refTput

    # INCREASE (lines 4-10): ratchet refTput on improvement.
    ch_inc = jnp.where(better, jnp.minimum(ch + d, mx), ch)
    ref_inc = jnp.where(better, tput, ref)
    st_inc = jnp.where(worse, fsm.WARNING, fsm.INCREASE)

    # WARNING (lines 11-17)
    ch_warn = jnp.where(ok, ch, jnp.maximum(ch - d, 1.0))
    st_warn = jnp.where(ok, fsm.INCREASE, fsm.RECOVERY)

    # RECOVERY (lines 18-26): restore + rebase refTput if bandwidth changed.
    ch_rec = jnp.where(ok, ch, jnp.minimum(ch + d, mx))
    ref_rec = jnp.where(ok, ref, tput)
    st_rec = jnp.asarray(fsm.INCREASE)

    in_inc = st == fsm.INCREASE
    in_warn = st == fsm.WARNING
    new_ch = jnp.where(in_inc, ch_inc, jnp.where(in_warn, ch_warn, ch_rec))
    new_st = jnp.where(in_inc, st_inc, jnp.where(in_warn, st_warn, st_rec))
    new_ref = jnp.where(in_inc, ref_inc, jnp.where(in_warn, ref, ref_rec))

    return ts._replace(fsm=new_st.astype(jnp.int32), num_ch=new_ch,
                       prev_num_ch=ch, ref=new_ref)


def eett_update(ts: TunerState, meas: Measurement, sla: SLA) -> TunerState:
    """Algorithm 6 — Energy-efficient target throughput (3-state FSM)."""
    tput = meas.avg_tput
    a, b, d = sla.alpha, sla.beta, sla.delta_ch * 1.0
    mx, tgt = sla.max_ch * 1.0, sla.target_tput_mbps
    st, ch = ts.fsm, ts.num_ch

    high = tput > (1.0 + b) * tgt
    low = tput < (1.0 - a) * tgt

    # INCREASE (lines 4-7): leave band -> RECOVERY.
    st_inc = jnp.where(jnp.logical_or(high, low), fsm.RECOVERY, fsm.INCREASE)

    # RECOVERY (lines 8-15): one corrective step, then back to INCREASE.
    ch_rec = jnp.where(high, jnp.maximum(ch - d, 1.0),
                       jnp.where(low, jnp.minimum(ch + d, mx), ch))
    st_rec = jnp.asarray(fsm.INCREASE)

    in_inc = st == fsm.INCREASE
    new_ch = jnp.where(in_inc, ch, ch_rec)
    new_st = jnp.where(in_inc, st_inc, st_rec)

    return ts._replace(fsm=new_st.astype(jnp.int32), num_ch=new_ch,
                       prev_num_ch=ch,
                       ref=jnp.asarray(tgt * 1.0, jnp.float32))


def ismail_target_update(ts: TunerState, meas: Measurement,
                         sla: SLA) -> TunerState:
    """Baseline target tuner of Ismail et al. (paper §V-B): single-channel
    start, +/-1 channel per timeout, no FSM, no slow-start correction.  Its
    documented weaknesses — very slow ramp and no remaining-size channel
    redistribution — are what EETT (Alg 6) fixes."""
    tput = meas.avg_tput
    tgt = sla.target_tput_mbps
    low = tput < (1.0 - sla.alpha) * tgt
    high = tput > (1.0 + sla.beta) * tgt
    ch = jnp.where(low, ts.num_ch + 1.0,
                   jnp.where(high, ts.num_ch - 1.0, ts.num_ch))
    ch = jnp.clip(ch, 1.0, sla.max_ch * 1.0)
    return ts._replace(num_ch=ch, prev_num_ch=ts.num_ch,
                       fsm=jnp.asarray(fsm.INCREASE, jnp.int32))


def update(ts: TunerState, meas: Measurement, profile: NetworkProfile,
           cpu: CpuProfile, sla, *, scaling: bool = True,
           policy: SLAPolicy = None) -> TunerState:
    """One controller tick: Slow Start / SLA tuner + Algorithm-3 load control.

    ``scaling=False`` disables frequency & core scaling (the Fig. 4 ablation).
    ``sla`` is a static :class:`SLA` or a traceable
    :class:`~repro.core.types.SLAParams` (then pass ``policy`` explicitly —
    it selects code paths and stays static under ``jit``/``vmap``).
    """
    policy = sla.policy if policy is None else policy
    in_ss = ts.fsm == fsm.SLOW_START

    if policy == SLAPolicy.ISMAIL_TARGET:
        # no slow-start correction: the baseline ramps from 1 channel
        ss = ts._replace(fsm=jnp.asarray(fsm.INCREASE, jnp.int32))
        tuned = ismail_target_update(ts, meas, sla)
        return TunerState(*[jnp.where(in_ss, s, t)
                            for s, t in zip(ss, tuned)])

    ss = slow_start(ts, meas, profile, sla, policy)
    if policy == SLAPolicy.MIN_ENERGY:
        tuned = me_update(ts, meas, sla)
    elif policy == SLAPolicy.MAX_THROUGHPUT:
        tuned = eemt_update(ts, meas, sla)
    else:
        tuned = eett_update(ts, meas, sla)

    merged = TunerState(*[jnp.where(in_ss, s, t) for s, t in zip(ss, tuned)])

    if scaling:
        cores, freq_idx = load_control(cpu, sla, meas.cpu_load,
                                       merged.cores, merged.freq_idx)
        merged = merged._replace(cores=cores, freq_idx=freq_idx)
    return merged
