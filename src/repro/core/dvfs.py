"""First-principles DVFS host physics: CV²f dynamic power + leakage.

The reference ``energy_model`` folds voltage into a calibrated cubic
(``k_dyn * f^3``); that reproduces the paper's RAPL numbers but hides the
quantity DVFS actually trades on — supply voltage.  This module models the
host the way circuit-level simulators (Lumos-style technology sweeps) do:

  * a **voltage-frequency curve** per silicon technology: ``V(f)`` sample
    points, linearly interpolated across the operating-point sweep (and
    clamped at the table edges).  Higher frequency demands higher voltage,
    which is where the superlinear energy cost of speed comes from;
  * **dynamic power** from first principles: ``P_dyn = C_eff · V² · f · a``
    with ``C_eff`` the per-core effective switched capacitance (nF — with
    volts and GHz this is numerically watts) and ``a`` the activity factor
    (per-core utilization);
  * an explicit **leakage split**: per awake core
    ``P_leak(V) = leak_w + leak_w_per_v · V`` (a linear proxy for the
    exponential V-dependence of subthreshold leakage), plus the package's
    constant uncore draw from the :class:`~repro.core.types.CpuProfile`;
  * **per-core-type constants**: the first ``n_big`` awake cores are big
    cores; cores beyond that are efficiency cores with fractions of a big
    core's throughput, capacitance, and leakage — the same asymmetry shape
    as ``repro.api.environments.BigLittleEnergyModel``, but now grounded in
    C and V rather than power ratios;
  * a **race-to-idle vs pace-to-deadline** accounting mode: in ``"race"``
    mode the idle fraction of each tick parks core leakage down to
    ``idle_leak_frac`` (deep C-states), rewarding finishing fast; in
    ``"pace"`` mode awake cores leak at full rate regardless of utilization
    — the regime where stretching work to the deadline at a lower V wins.

**Degeneration contract.**  :meth:`DvfsEnergyModel.matched` builds the
configuration whose tables collapse onto the reference model: ``V(f) = f``
numerically (so ``C·V²·f == k·f³``), capacitance ``core_dyn_w_per_ghz3``,
voltage-independent leakage ``core_static_w``, all-big cores, pace
accounting.  Every arithmetic expression below is grouped to match the
reference/big-little float32 op order, so the degeneration is *bit-exact*
(golden-tested in tests/test_dvfs.py) — the reference model is one point of
this model's parameter space, which is what makes the family a drop-in
physics upgrade rather than a parallel code path.

:class:`DvfsNetworkModel` pairs the energy model with the reference WAN
physics and adds a **native** ``step_arrays`` lowering (the fusion hook the
``NetworkModel`` protocol documents): the flat executors advance the packed
``TickLayout`` row directly instead of round-tripping through the pytree
adapters.  The V(f) tables materialize as trace-time constants
(:func:`repro.core.tickstate.const_table`), so the pallas executor hoists
them into the fused kernel as consts via the existing ``make_jaxpr``
machinery — no new kernel parameters required.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from . import network_model
from .tickstate import const_table
from .types import CpuProfile, SimState, freq_table, partition_sum

#: Lumos-style technology presets: a high-performance process ("hp" —
#: steep leakage, shallow V(f) slope, clocks high) and a low-power process
#: ("lp" — near-zero leakage but a steep V(f) wall past ~2 GHz).  Values
#: are calibrated so "hp" lands in the same watt range as the reference
#: model on the default CpuProfile (~15 W/core dynamic at 3 GHz, ~1 W/core
#: leakage), keeping the controllers' operating envelope comparable.
DVFS_TECHS = {
    "hp": dict(
        vf_ghz=(0.8, 1.2, 1.6, 2.0, 2.4, 2.8, 3.2),
        vf_volt=(0.65, 0.74, 0.83, 0.93, 1.04, 1.16, 1.29),
        cap_nf=3.9, leak_w=0.15, leak_w_per_v=0.8),
    "lp": dict(
        vf_ghz=(0.6, 1.0, 1.4, 1.8, 2.2, 2.6, 3.0),
        vf_volt=(0.72, 0.86, 1.01, 1.17, 1.34, 1.52, 1.71),
        cap_nf=3.4, leak_w=0.02, leak_w_per_v=0.12),
}

IDLE_MODES = ("race", "pace")


@dataclasses.dataclass(frozen=True)
class DvfsEnergyModel:
    """CV²f + leakage host power physics (see module docstring).

    Implements the full ``repro.api.environments.EnergyModel`` protocol.
    Frozen and hashable: instances join the engine's runner-cache /
    sweep-group keys, so two different V(f) tables compile two executables
    (environment knobs are static, like every other environment).
    """

    name = "dvfs"
    tech: str = "hp"                 # preset label (repr/meta only)
    vf_ghz: tuple = DVFS_TECHS["hp"]["vf_ghz"]
    vf_volt: tuple = DVFS_TECHS["hp"]["vf_volt"]
    cap_nf: float = DVFS_TECHS["hp"]["cap_nf"]      # C_eff per big core
    leak_w: float = DVFS_TECHS["hp"]["leak_w"]      # per-core leakage at V=0
    leak_w_per_v: float = DVFS_TECHS["hp"]["leak_w_per_v"]  # dP_leak/dV
    n_big: int = 8
    little_perf: float = 0.45        # little-core throughput / big-core
    little_cap_frac: float = 0.25    # little-core C_eff / big-core
    little_leak_frac: float = 0.5    # little-core leakage / big-core
    idle: str = "pace"               # "race" (race-to-idle) | "pace"
    idle_leak_frac: float = 0.05     # residual leakage while parked (race)
    max_freq_ghz: float | None = None  # DVFS governor cap on the ladder

    def __post_init__(self):
        if len(self.vf_ghz) != len(self.vf_volt) or len(self.vf_ghz) < 2:
            raise ValueError(
                f"V(f) table needs >= 2 matched (f, V) samples, got "
                f"{len(self.vf_ghz)} freqs / {len(self.vf_volt)} volts")
        if any(b <= a for a, b in zip(self.vf_ghz, self.vf_ghz[1:])):
            raise ValueError(f"vf_ghz must be strictly increasing, got "
                             f"{self.vf_ghz}")
        if any(v <= 0.0 for v in self.vf_volt):
            raise ValueError(f"vf_volt must be positive, got {self.vf_volt}")
        if self.cap_nf <= 0.0:
            raise ValueError(f"cap_nf must be positive, got {self.cap_nf}")
        if self.leak_w < 0.0 or self.leak_w_per_v < 0.0:
            raise ValueError("leakage constants must be >= 0, got "
                             f"leak_w={self.leak_w}, "
                             f"leak_w_per_v={self.leak_w_per_v}")
        if self.n_big < 1:
            raise ValueError(f"n_big must be >= 1, got {self.n_big}")
        for f in ("little_perf", "little_cap_frac", "little_leak_frac"):
            v = getattr(self, f)
            if not 0.0 < v <= 1.0:
                raise ValueError(f"{f} must be in (0, 1], got {v}")
        if self.idle not in IDLE_MODES:
            raise ValueError(f"idle must be one of {IDLE_MODES}, got "
                             f"{self.idle!r}")
        if not 0.0 <= self.idle_leak_frac <= 1.0:
            raise ValueError(f"idle_leak_frac must be in [0, 1], got "
                             f"{self.idle_leak_frac}")
        if self.max_freq_ghz is not None and self.max_freq_ghz <= 0.0:
            raise ValueError(f"max_freq_ghz must be positive (or None), "
                             f"got {self.max_freq_ghz}")

    @classmethod
    def for_tech(cls, tech: str = "hp", **overrides) -> "DvfsEnergyModel":
        """Build from a :data:`DVFS_TECHS` preset; kwargs override fields."""
        try:
            base = DVFS_TECHS[tech]
        except KeyError:
            raise KeyError(f"unknown DVFS technology {tech!r}; expected one "
                           f"of {tuple(sorted(DVFS_TECHS))}") from None
        return cls(tech=tech, **{**base, **overrides})

    @classmethod
    def matched(cls, cpu: CpuProfile) -> "DvfsEnergyModel":
        """The flat-table configuration that degenerates to the reference
        model bit-exactly on ``cpu``: V(f) = f (volts numerically equal to
        GHz, so C·V²·f reproduces k·f³), C_eff = ``core_dyn_w_per_ghz3``,
        voltage-independent per-core leakage = ``core_static_w``, every
        core big, pace accounting, no governor cap."""
        ladder = tuple(float(f) for f in cpu.freq_levels_ghz)
        return cls(tech="matched", vf_ghz=ladder, vf_volt=ladder,
                   cap_nf=cpu.core_dyn_w_per_ghz3,
                   leak_w=cpu.core_static_w, leak_w_per_v=0.0,
                   n_big=max(cpu.num_cores, 1), idle="pace")

    def code(self) -> "DvfsEnergyModel":
        return self

    # ------------------------------------------------------------ physics --

    def voltage(self, freq_ghz):
        """V(f): linear interpolation over the technology's sample points,
        clamped at the table edges.  Exact at the sample points (the
        interpolant returns the node value bit-for-bit), which is what
        makes the matched-tables degeneration exact."""
        return jnp.interp(freq_ghz, const_table(self.vf_ghz),
                          const_table(self.vf_volt))

    def _core_mix(self, cores):
        c = jnp.asarray(cores).astype(jnp.float32)
        big = jnp.minimum(c, float(self.n_big))
        little = jnp.maximum(c - float(self.n_big), 0.0)
        return big, little

    def operating_point(self, cpu, cores, freq_idx):
        f = freq_table(cpu)[jnp.clip(freq_idx, 0,
                                     len(cpu.freq_levels_ghz) - 1)]
        if self.max_freq_ghz is not None:
            f = jnp.minimum(f, jnp.float32(self.max_freq_ghz))
        c = jnp.clip(cores, 1, cpu.num_cores)
        return c, f

    def cpu_capacity_mbps(self, cpu, cores, freq_ghz, num_ch):
        big, little = self._core_mix(cores)
        core_eff = big + little * self.little_perf
        cpb = cpu.cycles_per_byte + cpu.cycles_per_byte_per_ch * num_ch
        return core_eff * freq_ghz * 1e9 * cpu.ipc / (cpb * 1e6)

    def cpu_load(self, cpu, tput_mbps, cores, freq_ghz, num_ch):
        cap = self.cpu_capacity_mbps(cpu, cores, freq_ghz, num_ch)
        return jnp.clip(tput_mbps / jnp.maximum(cap, 1e-6), 0.0, 1.0)

    def power_w(self, cpu, cores, freq_ghz, util, tput_mbps):
        big, little = self._core_mix(cores)
        u = jnp.clip(util, 0.0, 1.0)
        v = self.voltage(freq_ghz)
        # Grouping matters: (v * v) * f commutes bitwise with the
        # integer_pow lowering of the reference model's f**3, which is what
        # keeps the matched-tables degeneration exact in float32.
        dyn = ((big + little * self.little_cap_frac)
               * self.cap_nf * ((v * v) * freq_ghz) * u)
        per_core = self.leak_w + self.leak_w_per_v * v
        if self.idle == "race":
            # Idle core-time drops into deep C-states: only idle_leak_frac
            # of the leakage survives the parked fraction of the tick.
            per_core = per_core * (u + self.idle_leak_frac * (1.0 - u))
        static = (cpu.pkg_static_w
                  + (big + little * self.little_leak_frac) * per_core)
        mem = cpu.mem_w_per_mbps * tput_mbps
        return static + dyn + mem

    def energy_per_mb(self, cpu, cores, freq_ghz, tput_mbps, num_ch):
        """J/MB at steady state (operating-point sweep helper)."""
        util = self.cpu_load(cpu, tput_mbps, cores, freq_ghz, num_ch)
        p = self.power_w(cpu, cores, freq_ghz, util, tput_mbps)
        return p / jnp.maximum(tput_mbps, 1e-6)


@dataclasses.dataclass(frozen=True)
class DvfsNetworkModel:
    """Reference WAN physics with a native flat-row tick.

    The pytree ``step`` delegates to ``repro.core.network_model`` — the
    DVFS family changes host physics, not the wire.  ``step_arrays`` is the
    protocol's native lowering: the same arithmetic, op for op, expressed
    directly on the packed f32 ``SimState`` row of a
    :class:`~repro.core.tickstate.TickLayout`, so the ``blocked`` and
    ``pallas`` executors skip the pack/unpack adapter round-trip entirely.
    Bit-identity with the pytree path is guaranteed by construction (the
    adapters are pure slicing/concatenation and the op order is identical)
    and regression-tested in tests/test_dvfs.py.
    """

    name = "dvfs"

    def code(self) -> "DvfsNetworkModel":
        return self

    def init_state(self, total_mb, net) -> SimState:
        return network_model.init_state(total_mb, net)

    def step(self, energy, net, cpu, state, params, avg_file_mb, dt,
             bw_scale):
        return network_model.step(net, cpu, state, params, avg_file_mb, dt,
                                  bw_scale, energy=energy)

    def step_arrays(self, lay, energy, net, cpu, sim_row, params,
                    avg_file_mb, dt, bw_scale):
        p = lay.n_partitions
        remaining = sim_row[..., 0:p]
        window = sim_row[..., p:2 * p]

        # Mirrors network_model.step exactly — same ops, same order — on
        # the row slices instead of SimState fields.
        active = (remaining > 0.0).astype(jnp.float32)          # [P]
        cc = jnp.maximum(params.cc, 0.0) * active
        total_ch = partition_sum(cc)

        n_active = jnp.maximum(partition_sum(active), 1.0)
        avg_win = partition_sum(window * active) / n_active
        r1 = network_model.channel_rate(net, window, avg_file_mb,
                                        params.pp, params.par)
        demand = cc * r1                                        # [P]
        total_demand = partition_sum(demand)

        b_avail = net.bandwidth_mbps * (1.0 - net.cross_traffic) * bw_scale
        eff = network_model.contention_efficiency(net, total_ch, avg_win)
        net_cap = b_avail * eff

        cores, f = energy.operating_point(cpu, params.cores, params.freq_idx)
        cpu_cap = energy.cpu_capacity_mbps(cpu, cores, f, total_ch)

        tput = jnp.minimum(jnp.minimum(total_demand, net_cap), cpu_cap)
        scale = tput / jnp.maximum(total_demand, 1e-6)
        part_rate = demand * scale                              # [P]

        moved = jnp.minimum(part_rate * dt, remaining)

        ramp = jnp.clip(dt / (8.0 * net.rtt_s), 0.0, 1.0)
        new_window = window + (net.avg_window_mb - window) * ramp

        load = energy.cpu_load(cpu, tput, cores, f, total_ch)
        pw = energy.power_w(cpu, cores, f, load, tput)

        # Same layout as TickLayout.pack_sim: [remaining | window | scalars].
        row = jnp.concatenate([
            remaining - moved,
            new_window,
            jnp.stack([sim_row[..., lay.off_t] + dt,
                       sim_row[..., lay.off_energy] + pw * dt,
                       sim_row[..., lay.off_bytes]
                       + partition_sum(moved)]),
        ])
        out = network_model.NetOut(tput_mbps=tput, part_rate=part_rate,
                                   cpu_load=load, power_w=pw,
                                   num_ch=total_ch)
        return row, out
