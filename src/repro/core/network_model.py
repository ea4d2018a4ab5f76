"""Discrete-time wide-area transfer simulator (JAX, scan-friendly).

Reproduces the substrate the paper runs on (Table I testbeds / Table II
datasets) as a deterministic per-tick model:

  * per-channel TCP rate  = window / RTT, with slow-start window ramp;
  * pipelining  (pp)  amortizes the 1-RTT-per-file control cost of small files;
  * parallelism (par) multiplies the effective window of large files (up to
    the file/buffer ratio — mirroring the Ismail-et-al. pathology where
    buffer == BDP forces par -> 1);
  * concurrency (cc)  opens more channels, subject to a contention knee past
    the saturation point (over-concurrency *lowers* throughput — §II);
  * the CPU operating point (cores, freq) caps achievable throughput and
    sets power draw (energy_model).

All functions are pure and jit/vmap-safe; one whole transfer is a single
``lax.scan`` over ticks (see engine.py).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from . import energy_model
from .types import (CpuProfile, NetworkProfile, SimState, TransferParams,
                    partition_sum)


class NetOut(NamedTuple):
    tput_mbps: jnp.ndarray       # [] total achieved throughput
    part_rate: jnp.ndarray       # [P] per-partition rates
    cpu_load: jnp.ndarray        # []
    power_w: jnp.ndarray         # []
    num_ch: jnp.ndarray          # [] total active channels


def channel_rate(profile: NetworkProfile, window_mb, avg_file_mb, pp, par):
    """Achievable MB/s of ONE channel of a partition (before contention)."""
    # Parallelism multiplies the window, but only while chunks still exceed
    # the socket buffer; past that, extra streams add nothing (paper §II).
    par_eff = jnp.clip(par, 1.0, jnp.maximum(avg_file_mb / profile.buffer_mb, 1.0))
    raw = par_eff * window_mb / profile.rtt_s
    # Pipelining: each file costs rtt/pp of dead time on the channel.
    per_file_s = avg_file_mb / jnp.maximum(raw, 1e-6) + profile.rtt_s / jnp.maximum(pp, 1.0)
    return avg_file_mb / jnp.maximum(per_file_s, 1e-9)


def contention_efficiency(profile: NetworkProfile, total_ch, window_mb):
    """Network efficiency in (0,1]: drops once channels exceed saturation."""
    per_ch = jnp.maximum(window_mb / profile.rtt_s, 1e-6)
    c_sat = profile.loss_knee * profile.bandwidth_mbps / per_ch
    over = jnp.maximum(total_ch - c_sat, 0.0) / jnp.maximum(c_sat, 1.0)
    return 1.0 / (1.0 + 0.5 * over * over)


def step(
    profile: NetworkProfile,
    cpu: CpuProfile,
    state: SimState,
    params: TransferParams,
    avg_file_mb,
    dt: float,
    bw_scale,
    energy=None,
):
    """Advance the transfer by ``dt`` seconds. Returns (state', NetOut).

    ``avg_file_mb`` is the per-partition average file (or chunk) size —
    static dataset metadata threaded through by engine.py.  ``energy``
    supplies the host power physics (anything implementing the
    ``repro.api.environments.EnergyModel`` protocol); it defaults to this
    package's reference ``energy_model`` module, whose functions have the
    exact protocol signatures.
    """
    if energy is None:
        energy = energy_model
    active = (state.remaining_mb > 0.0).astype(jnp.float32)     # [P]
    cc = jnp.maximum(params.cc, 0.0) * active
    total_ch = partition_sum(cc)

    # Contention sees only the partitions that still hold channels: drained
    # partitions' windows keep ramping toward the profile window and would
    # otherwise skew the saturation estimate late in the transfer.
    n_active = jnp.maximum(partition_sum(active), 1.0)
    avg_win = partition_sum(state.window_mb * active) / n_active
    r1 = channel_rate(profile, state.window_mb, avg_file_mb, params.pp, params.par)
    demand = cc * r1                                            # [P]
    total_demand = partition_sum(demand)

    b_avail = profile.bandwidth_mbps * (1.0 - profile.cross_traffic) * bw_scale
    eff = contention_efficiency(profile, total_ch, avg_win)
    net_cap = b_avail * eff

    cores, f = energy.operating_point(cpu, params.cores, params.freq_idx)
    cpu_cap = energy.cpu_capacity_mbps(cpu, cores, f, total_ch)

    tput = jnp.minimum(jnp.minimum(total_demand, net_cap), cpu_cap)
    scale = tput / jnp.maximum(total_demand, 1e-6)
    part_rate = demand * scale                                  # [P]

    # Drain partitions; surplus reallocation within one tick is a
    # second-order effect we ignore (dt is small).
    moved = jnp.minimum(part_rate * dt, state.remaining_mb)
    remaining = state.remaining_mb - moved

    # TCP window slow-start ramp toward the profile's steady-state window.
    ramp = jnp.clip(dt / (8.0 * profile.rtt_s), 0.0, 1.0)
    window = state.window_mb + (profile.avg_window_mb - state.window_mb) * ramp

    load = energy.cpu_load(cpu, tput, cores, f, total_ch)
    pw = energy.power_w(cpu, cores, f, load, tput)

    new_state = SimState(
        remaining_mb=remaining,
        window_mb=window,
        t=state.t + dt,
        energy_j=state.energy_j + pw * dt,
        bytes_moved=state.bytes_moved + partition_sum(moved),
    )
    out = NetOut(tput_mbps=tput, part_rate=part_rate, cpu_load=load,
                 power_w=pw, num_ch=total_ch)
    return new_state, out


def init_state(total_mb, profile: NetworkProfile) -> SimState:
    """Fresh simulation state; windows start small (TCP slow start)."""
    total_mb = jnp.asarray(total_mb, jnp.float32)
    p = total_mb.shape[0]
    return SimState(
        remaining_mb=total_mb,
        window_mb=jnp.full((p,), 64.0 / 1024.0, jnp.float32),  # 64 KB
        t=jnp.zeros((), jnp.float32),
        energy_j=jnp.zeros((), jnp.float32),
        bytes_moved=jnp.zeros((), jnp.float32),
    )
