"""Core datatypes for the SLA-driven transfer-tuning framework.

Everything here is either a static (hashable) config dataclass or a JAX pytree
(NamedTuple of arrays), so the whole simulation + controller stack can live
under ``jax.jit`` / ``jax.lax.scan`` / ``jax.vmap``.

Units convention (internal):
    bytes   -> MB (float32)
    time    -> seconds
    rate    -> MB/s
    power   -> watts
    energy  -> joules
    freq    -> GHz
"""
from __future__ import annotations

import dataclasses
import enum
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

MB = 1.0
GB = 1024.0
KB = 1.0 / 1024.0


class SLAPolicy(enum.IntEnum):
    """Service-level agreement requested by the client (paper §IV)."""

    MIN_ENERGY = 0          # ME   (Algorithm 4)
    MAX_THROUGHPUT = 1      # EEMT (Algorithm 5)
    TARGET_THROUGHPUT = 2   # EETT (Algorithm 6)
    ISMAIL_TARGET = 3       # baseline: Ismail et al. target tuner (§V-B) —
                            # starts at 1 channel, +/-1 per tick, static
                            # channel distribution, no freq/core scaling


@dataclasses.dataclass(frozen=True)
class SLA:
    """SLA + tuner hyper-parameters (α, β, Δch, timeout of Algorithms 4-6)."""

    policy: SLAPolicy = SLAPolicy.MAX_THROUGHPUT
    target_tput_mbps: float = 0.0      # only for TARGET_THROUGHPUT, MB/s
    alpha: float = 0.10                # negative-feedback tolerance
    beta: float = 0.05                 # positive-feedback threshold
    delta_ch: int = 2                  # ΔCh channel increment
    max_ch: int = 64                   # maxCh
    timeout_s: float = 1.0             # controller tick ("Timeout")
    max_load: float = 0.85             # Algorithm 3 maxLoad
    min_load: float = 0.40             # Algorithm 3 minLoad


@dataclasses.dataclass(frozen=True)
class NetworkProfile:
    """A testbed network (paper Table I)."""

    name: str = "chameleon"
    bandwidth_mbps: float = 1250.0       # 10 Gbps
    rtt_s: float = 0.032
    avg_window_mb: float = 2.0           # average TCP window (iperf estimate)
    buffer_mb: float = 4.0               # socket buffer size
    loss_knee: float = 1.35              # over-concurrency contention knee
    cross_traffic: float = 0.0           # fraction of bandwidth stolen (0..1)

    @property
    def bdp_mb(self) -> float:
        return self.bandwidth_mbps * self.rtt_s


@dataclasses.dataclass(frozen=True)
class CpuProfile:
    """End-system host CPU (the paper's Haswell/Broadwell clients)."""

    name: str = "haswell"
    num_cores: int = 8
    freq_levels_ghz: tuple = (1.2, 1.5, 1.8, 2.1, 2.4, 2.7, 3.0)
    ipc: float = 1.6                      # sustained instructions/cycle
    cycles_per_byte: float = 14.0         # protocol+copy cost of the transfer path
    cycles_per_byte_per_ch: float = 0.08  # per-extra-channel overhead
    pkg_static_w: float = 6.0             # package uncore/idle power
    core_static_w: float = 1.0            # per awake core (leakage)
    core_dyn_w_per_ghz3: float = 0.55     # ~15 W/core at 3 GHz full load
    mem_w_per_mbps: float = 0.004         # DRAM power ~ bytes moved

    @property
    def min_freq(self) -> float:
        return self.freq_levels_ghz[0]

    @property
    def max_freq(self) -> float:
        return self.freq_levels_ghz[-1]


@dataclasses.dataclass(frozen=True)
class DatasetSpec:
    """A file partition (paper Table II row). Static metadata."""

    name: str
    num_files: int
    total_mb: float
    avg_file_mb: float
    std_file_mb: float = 0.0


# Canonical paper datasets (Table II).
SMALL_FILES = DatasetSpec("small", 20_000, 1.94 * GB, 101.92 * KB, 29.06 * KB)
MEDIUM_FILES = DatasetSpec("medium", 5_000, 11.70 * GB, 2.40, 0.27)
LARGE_FILES = DatasetSpec("large", 128, 27.85 * GB, 222.78, 15.19)
MIXED = (SMALL_FILES, MEDIUM_FILES, LARGE_FILES)

# Canonical paper testbeds (Table I).
CHAMELEON = NetworkProfile("chameleon", 1250.0, 0.032, avg_window_mb=2.5, buffer_mb=8.0)
CLOUDLAB = NetworkProfile("cloudlab", 125.0, 0.036, avg_window_mb=1.0, buffer_mb=2.0)
DIDCLAB = NetworkProfile("didclab", 125.0, 0.044, avg_window_mb=1.0, buffer_mb=2.0)
TESTBEDS = {"chameleon": CHAMELEON, "cloudlab": CLOUDLAB, "didclab": DIDCLAB}


class NetParams(NamedTuple):
    """Numeric (traceable) view of a :class:`NetworkProfile`.

    Same attribute names as the profile, but every field is a scalar array so
    whole testbed grids can be ``vmap``-ed in one compiled executable.  All
    simulator code is duck-typed over either form.
    """

    bandwidth_mbps: jnp.ndarray
    rtt_s: jnp.ndarray
    avg_window_mb: jnp.ndarray
    buffer_mb: jnp.ndarray
    loss_knee: jnp.ndarray
    cross_traffic: jnp.ndarray

    @property
    def bdp_mb(self):
        return self.bandwidth_mbps * self.rtt_s

    @classmethod
    def from_profile(cls, profile: "NetworkProfile") -> "NetParams":
        # Host-side scalars: these cross to the device inside the jitted
        # engine runner, so allocating device arrays here would only add a
        # round-trip per leaf during scenario prep.
        return cls(*[np.float32(getattr(profile, f)) for f in cls._fields])


class SLAParams(NamedTuple):
    """Numeric (traceable) view of an :class:`SLA`.

    Mirrors the SLA attribute names used inside the controller tick so tuner
    hyper-parameters (and the EETT target) can vary across a vmap batch.
    ``policy`` and ``timeout_s`` stay static: the former selects code, the
    latter sets the host-side controller-tick stride.
    """

    target_tput_mbps: jnp.ndarray
    alpha: jnp.ndarray
    beta: jnp.ndarray
    delta_ch: jnp.ndarray
    max_ch: jnp.ndarray
    max_load: jnp.ndarray
    min_load: jnp.ndarray

    @classmethod
    def from_sla(cls, sla: "SLA") -> "SLAParams":
        return cls(*[np.float32(getattr(sla, f)) for f in cls._fields])


class TransferParams(NamedTuple):
    """The five jointly-tuned application-level parameters (paper §II).

    ``cc`` is per-partition (concurrency per dataset); ``pp``/``par`` are
    per-partition as well since Algorithm 1 derives them from avg file size.
    Algorithm 1 and the static baselines build them on the host, as numpy
    arrays; the engine receives them inside its traced inputs.
    """

    pp: np.ndarray         # [P] float32 pipelining depth per partition
    par: np.ndarray        # [P] float32 parallelism (chunks/file)
    cc: np.ndarray         # [P] float32 concurrent channels per partition
    cores: np.ndarray      # [] int32 active core count
    freq_idx: np.ndarray   # [] int32 index into freq_levels_ghz


def _fold_partitions(x):
    """Left fold over the trailing axis, as a chain of elementwise adds."""
    acc = x[..., 0]
    for i in range(1, x.shape[-1]):
        acc = acc + x[..., i]
    return acc


def _reduce_partitions(x):
    return jnp.sum(x, axis=-1)


def partition_sum(x):
    """Sum over the trailing partition axis, in index order on every backend
    and at every batch width.

    Every per-tick sum over partitions goes through here, so that a lane's
    f32 bits do not depend on how many lanes share its launch.  XLA:TPU
    lowers a ``jnp.sum`` over this axis in a layout-dependent order, and a
    one-lane batch gets another layout than a wider one, so there the sum is
    an explicit chain of adds, whose order is fixed by the static partition
    count.  XLA:CPU's reduce already adds in index order, and contracts
    each product feeding it into an FMA the same way whatever the fusion;
    an explicit chain of adds there would be contracted by LLVM differently
    from one fusion to the next, so the CPU keeps the reduce.
    """
    return jax.lax.platform_dependent(x, cpu=_reduce_partitions,
                                      default=_fold_partitions)


class SimState(NamedTuple):
    """Dynamic state of the discrete-time transfer simulation.

    The engine freezes the whole state at the completion tick (see
    ``repro.core.engine``): after the last partition drains, ``t`` stops
    advancing and ``energy_j`` stops accumulating, so the final state
    describes the *transfer*, not the padded simulation horizon.
    """

    remaining_mb: jnp.ndarray   # [P] bytes left per partition
    window_mb: jnp.ndarray      # [P] current avg TCP window per channel
    t: jnp.ndarray              # [] elapsed seconds (frozen at completion)
    energy_j: jnp.ndarray       # [] cumulative energy (frozen at completion)
    bytes_moved: jnp.ndarray    # [] cumulative MB


class TunerState(NamedTuple):
    """State of the FSM controller (Algorithms 4-6) + load control."""

    fsm: jnp.ndarray            # [] int32 FSM state
    num_ch: jnp.ndarray         # [] float32 total channel budget
    prev_num_ch: jnp.ndarray    # [] float32 (for Recovery restore)
    ref: jnp.ndarray            # [] float32 refTput (EEMT) / E_past (ME)
    cores: jnp.ndarray          # [] int32
    freq_idx: jnp.ndarray       # [] int32
    # measurement accumulators since the last controller tick
    acc_mb: jnp.ndarray         # [] float32
    acc_j: jnp.ndarray          # [] float32
    acc_s: jnp.ndarray          # [] float32


class TickMetrics(NamedTuple):
    """Per-step observables emitted by the engine scan.

    ``done[i]`` is recorded *after* step ``i``: it is True from the tick
    during which the transfer drained (completion time ``(i + 1) * dt``).
    All other fields are masked to zero on post-completion ticks, so traces
    from the early-exit and full-horizon engine paths are bit-identical.
    """

    tput_mbps: jnp.ndarray
    power_w: jnp.ndarray
    cpu_load: jnp.ndarray
    num_ch: jnp.ndarray
    cores: jnp.ndarray
    freq_ghz: jnp.ndarray
    done: jnp.ndarray


def dataset_arrays(specs) -> dict:
    """Pack static dataset metadata into arrays for the simulator."""
    specs = tuple(specs)
    return dict(
        total_mb=jnp.array([s.total_mb for s in specs], jnp.float32),
        avg_file_mb=jnp.array([s.avg_file_mb for s in specs], jnp.float32),
        num_files=jnp.array([s.num_files for s in specs], jnp.float32),
    )


def freq_table(cpu: CpuProfile) -> jnp.ndarray:
    return jnp.asarray(np.asarray(cpu.freq_levels_ghz, np.float32))
