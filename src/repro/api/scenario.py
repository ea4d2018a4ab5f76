"""Scenario: one declarative transfer experiment; run one, or sweep a grid.

``sweep`` is the headline: it groups scenarios whose compiled code is
identical (same controller code path, environment code, CPU model, step
count, tick stride and partition count), stacks each group's numeric
inputs, and executes the group
as ONE vmapped XLA launch of the early-exiting engine.  A 72-cell figure
grid becomes a handful of compiled executables instead of 72 sequential jit
calls — and each executable stops scanning as soon as every lane of its
batch has drained, instead of burning the full padded ``total_s`` horizon.

On hosts with more than one accelerator device, groups with at least one
lane per device are additionally sharded across devices: the stacked batch
is padded to a multiple of the device count, and to two lanes per device
at least (:func:`repro.distributed.sharding.pad_batch`), placed with a
``batch``-sharded layout, and run through a ``shard_map``-wrapped runner
whose input buffers are donated.  Each device early-exits on its own shard
independently.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Any, NamedTuple, Optional, Sequence

import jax
import numpy as np

from repro import obs
from repro.core import engine
from repro.core.engine import ScanInputs, TransferResult
from repro.core.types import CpuProfile, NetworkProfile

from .controllers import Controller, as_controller
from .environments import Environment, as_environment


@dataclasses.dataclass(frozen=True, eq=False)
class Scenario:
    """Everything one transfer experiment needs, bundled and frozen.

    ``controller`` accepts anything :func:`as_controller` does — a Controller
    instance, a registry name ("eemt", "wget/curl", ...), or a legacy SLA /
    StaticController object.  ``environment`` accepts anything
    :func:`as_environment` does — ``None`` (the reference physics), an
    Environment, a registry name ("lossy-wan", "big-little", ...), or a bare
    NetworkModel / EnergyModel.

    ``total_s`` is a *budget*, not a cost: the engine freezes all accounting
    at the completion tick and stops simulating shortly after (chunked early
    exit), so ``energy_j`` / ``time_s`` / ``avg_power_w`` of a completed
    transfer are invariant to how generous the horizon was.

    ``executor`` selects the engine lowering (``repro.core.engine``):
    ``"auto"`` (the default) is ``blocked`` on every backend, and every
    executor is bit-identical — it is a performance knob, not a semantics
    knob.  It joins the sweep group key, so mixing executors in one sweep simply
    splits groups.

    ``bw_schedule`` is ``None``, the full path rate (the engine's input
    ``ScanInputs.bw`` is then ``[]``, one share every tick sees), or an
    ``[n_steps]`` share of the path's bandwidth per tick.  A scheduled
    scenario and an unscheduled one never share an executable.

    ``eq=False``: scenarios may carry an ndarray ``bw_schedule``, so equality
    and hashing are by identity (array fields would make ``==`` ambiguous).
    """

    profile: NetworkProfile
    datasets: tuple
    controller: Any
    cpu: CpuProfile = CpuProfile()
    environment: Optional[Any] = None   # None -> reference physics
    total_s: float = 3600.0
    dt: float = 0.1
    bw_schedule: Optional[Any] = None   # None (full rate) or [n_steps] share
    name: Optional[str] = None
    executor: str = "auto"              # engine lowering (see repro.core)

    def __post_init__(self):
        object.__setattr__(self, "datasets", tuple(self.datasets))
        # Validate here, where the mistake is made: bad values otherwise
        # surface as NaNs or shape errors deep inside the jitted engine.
        if not self.datasets:
            raise ValueError("Scenario needs at least one dataset")
        if not self.dt > 0.0:
            raise ValueError(f"dt must be positive, got {self.dt}")
        if self.total_s < self.dt:
            raise ValueError(f"total_s ({self.total_s}) must cover at least "
                             f"one tick of dt ({self.dt})")
        # Validate the executor name eagerly (resolution happens again at
        # run time, against the backend in use then).
        engine.resolve_executor(self.executor)


class _GroupKey(NamedTuple):
    """Executable-group key: everything that selects compiled code."""

    ctrl_code: Controller
    env_code: Environment
    cpu: CpuProfile
    n_steps: int
    dt: float
    ctrl_every: int
    n_partitions: int
    executor: str
    scheduled: bool     # ``bw`` is an [n_steps] schedule, not a [] share


def ctrl_stride(ctrl: Controller, dt: float) -> int:
    """Engine ticks between controller invocations (the "Timeout" stride).

    Shared by the sweep group key and the fleet wave scheduler so a transfer
    ticks its controller at the same absolute step indices on either path.
    """
    return max(int(round(ctrl.timeout_s / dt)), 1) if ctrl.tunes else 1


def _group_key(ctrl: Controller, env: Environment, sc: Scenario,
               n_partitions: int) -> _GroupKey:
    """Single source of truth for both ``_prepare`` (actual grouping) and
    ``group_count`` (prediction)."""
    n_steps = int(round(sc.total_s / sc.dt))
    # Resolve "auto" here so an auto scenario groups (and shares a compiled
    # executable) with one that named the same executor explicitly.
    return _GroupKey(ctrl.code(), env.code(), sc.cpu, n_steps, sc.dt,
                     ctrl_stride(ctrl, sc.dt), n_partitions,
                     engine.resolve_executor(sc.executor),
                     sc.bw_schedule is not None)


class _Prepared(NamedTuple):
    key: _GroupKey
    inputs: ScanInputs      # numeric pytree (numpy leaves)
    name: str
    total_s: float
    dt: float


def _prepare(sc: Scenario) -> _Prepared:
    ctrl: Controller = as_controller(sc.controller)
    env = as_environment(sc.environment)
    ci = ctrl.init(sc.datasets, sc.profile, sc.cpu)
    key = _group_key(ctrl, env, sc, len(ci.specs))

    inputs = ScanInputs.from_init(ci, sc.profile)
    if key.scheduled:
        bw = np.asarray(sc.bw_schedule, np.float32)
        if bw.shape != (key.n_steps,):
            raise ValueError(
                f"bw_schedule shape {bw.shape} != ({key.n_steps},)")
        inputs = inputs._replace(bw=bw)
    inputs = jax.tree.map(np.asarray, inputs)
    return _Prepared(key=key, inputs=inputs,
                     name=sc.name or ctrl.name,
                     total_s=sc.total_s, dt=sc.dt)


def _postprocess(sim, done_at, prep: _Prepared) -> TransferResult:
    """One lane's result from its final state and completion tick; the
    result carries no traces (``run`` attaches its own)."""
    sim = jax.tree.map(np.asarray, sim)
    # Completion comes from the final state; ``done_at`` (-1 while live)
    # only dates it.
    completed = bool(np.sum(sim.remaining_mb) <= 0.0)
    if completed:
        # The transfer drained DURING tick ``done_at``, i.e. at time
        # (done_at + 1) * dt.  (A transfer finishing on tick 0 took one dt,
        # not zero seconds.)
        t_done = float(prep.dt * (int(done_at) + 1))
    else:
        t_done = float(prep.total_s)
    energy = float(sim.energy_j)
    moved = float(sim.bytes_moved)
    avg_tput = moved / max(t_done, 1e-9)
    avg_power = energy / max(t_done, 1e-9)
    return TransferResult(
        name=prep.name,
        time_s=t_done,
        energy_j=energy,
        avg_tput_MBps=avg_tput,
        avg_tput_gbps=avg_tput * 8.0 / 1000.0,
        avg_power_w=avg_power,
        completed=completed,
    )


# ScanInputs leaves with a leading partition axis (everything else in the
# pytree is scalar per scenario).
_PARTITION_FIELDS = ("pp", "par", "total_mb", "avg_file_mb", "static_w")


def pad_partition_inputs(inputs: ScanInputs,
                         n_partitions: int) -> ScanInputs:
    """Widen ``ScanInputs`` to ``n_partitions`` with zero-byte partitions.

    A zero-byte partition is born drained: it gets no channels, contributes
    zero demand/bytes/energy, and the contention estimate averages over
    active partitions only — so padding is a bit-exact no-op on the results.
    ``sweep`` uses it to merge scenarios with different dataset counts into
    one compiled executable; the fleet wave scheduler
    (``repro.fleet.scheduler``) uses it to make every transfer in a trace
    shape-compatible regardless of its dataset count.
    """
    p = len(np.asarray(inputs.total_mb))
    if p == n_partitions:
        return inputs
    if p > n_partitions:
        raise ValueError(f"cannot shrink {p} partitions to {n_partitions}")
    pad = n_partitions - p
    return inputs._replace(**{
        f: np.concatenate([np.asarray(getattr(inputs, f)),
                           np.zeros(pad, np.float32)])
        for f in _PARTITION_FIELDS})


def _pad_partitions(prep: _Prepared, n_partitions: int) -> _Prepared:
    """Widen a prepared scenario to ``n_partitions`` (see
    :func:`pad_partition_inputs`)."""
    if prep.key.n_partitions == n_partitions:
        return prep
    return prep._replace(
        key=prep.key._replace(n_partitions=n_partitions),
        inputs=pad_partition_inputs(prep.inputs, n_partitions))


def _merged_partition_counts(keys) -> dict:
    """The padding policy shared by ``sweep`` and ``group_count``: each key
    is widened to the maximum partition count among the keys it could share
    an executable with (same key modulo partition count)."""
    p_max: dict[_GroupKey, int] = {}
    for k in keys:
        base = k._replace(n_partitions=0)
        p_max[base] = max(p_max.get(base, 0), k.n_partitions)
    return {k: p_max[k._replace(n_partitions=0)] for k in keys}


def _fetch(out, batch: Optional[int] = None):
    """Wait for a runner's ``(sim, ts, tail)``, then copy ``sim`` and
    ``tail`` (the completion ticks, or ``api.run``'s per-tick metrics) to
    the host, cut to ``batch`` lanes if given."""
    with obs.span("sweep.wait"):
        jax.block_until_ready(out)
    sim, _, tail = out
    kept = (sim, tail)
    if batch is None:
        n_bytes = _nbytes(kept)
    else:
        n_bytes = sum(x.nbytes // x.shape[0] * batch
                      for x in jax.tree.leaves(kept))
    with obs.span("sweep.fetch", bytes=n_bytes):
        # ``device_get`` starts every leaf's copy before it waits on any.
        host = jax.device_get(kept)
        if batch is None:
            return host
        return jax.tree.map(lambda x: x[:batch], host)


def _nbytes(tree) -> int:
    """Bytes of a pytree's array leaves (host or device)."""
    return sum(x.nbytes for x in jax.tree.leaves(tree))


def run(scenario: Scenario) -> TransferResult:
    """Run one scenario to completion (or its ``total_s`` timeout) on the
    unbatched cached runner; the result holds its per-tick traces."""
    prep = _prepare(scenario)
    k = prep.key
    with obs.span("sweep.launch", bytes=_nbytes(prep.inputs)):
        runner = engine.get_runner(k.ctrl_code, k.env_code, k.cpu,
                                   k.n_steps, k.dt, k.ctrl_every,
                                   batched=False, executor=k.executor)
        out = runner(prep.inputs)
    sim, metrics = _fetch(out)
    with obs.span("sweep.postprocess"):
        # ``done[i]`` is recorded post-step, so its first True is the
        # completion tick the trace-free runners carry.
        r = _postprocess(sim, np.argmax(metrics.done), prep)
        return dataclasses.replace(r, metrics=metrics)


def _run_group(key: _GroupKey, stacked, batch: int, devices):
    """Execute one stacked group on a trace-free runner, sharding across
    devices when possible.

    Returns ``(sim, done_at)``: the final state's pytree and the completion
    ticks, numpy leaves with a leading batch axis of exactly ``batch``
    (device padding stripped).
    """
    from repro.distributed import sharding as shd
    with obs.span("sweep.launch") as launch:
        shards = len(devices) if shd.should_shard(batch, devices) else 1
        # Every program runs two lanes or more on each device.  XLA folds a
        # batch axis of one away, and the program it then compiles may round
        # a multiply-add twice where the wider programs fuse it into one
        # FMA, so a lane's bits would depend on its group's size.
        stacked, _ = shd.pad_batch(
            stacked, shards * (2 if batch < 2 * shards else 1))
        launch.annotate(bytes=_nbytes(stacked))
        if shards > 1:
            mesh = shd.batch_mesh(devices)
            runner = engine.get_sharded_runner(
                key.ctrl_code, key.env_code, key.cpu, key.n_steps, key.dt,
                key.ctrl_every, tuple(devices), executor=key.executor)
            out = runner(shd.shard_batch(stacked, mesh))
        else:
            runner = engine.get_runner(key.ctrl_code, key.env_code, key.cpu,
                                       key.n_steps, key.dt, key.ctrl_every,
                                       batched=True, traces=False,
                                       executor=key.executor)
            out = runner(stacked)
    return _fetch(out, batch)


def sweep(scenarios: Sequence[Scenario], *,
          devices: Optional[Sequence] = None) -> list[TransferResult]:
    """Run many scenarios, batching shape-compatible ones into one launch.

    Results come back in input order.  Scenarios group when their compiled
    code is identical; each group executes as one vmapped call of the
    early-exiting engine, of two lanes or more (see ``_run_group``).
    The runners are trace-free: each carries its lanes' completion ticks
    instead of per-tick traces, so only the final state and one int32 per
    lane come back to the host, and results hold no ``metrics`` (``run``
    keeps them).  A lane's result does not depend on its group's size.

    ``devices`` selects the devices groups shard across (default: all local
    devices).  With more than one device, each group with at least one lane
    per device is padded to a multiple of the device count and dispatched
    through a ``shard_map`` runner with donated input buffers; on a single
    device — or with an explicitly empty ``devices`` sequence — the plain
    vmapped runner is used and results are identical.

    Each call opens the host spans of :mod:`repro.obs`: ``sweep``, with
    ``sweep.prepare`` and one ``sweep.group`` per executable holding
    ``sweep.launch``, ``sweep.wait``, ``sweep.fetch`` and
    ``sweep.postprocess``.
    """
    if devices is None:
        devices = jax.devices()
    # An explicitly empty device list means "no sharding": normalize it
    # here so the single-device fallback is a deliberate branch, not an
    # accident of the len(devices) > 1 guard.
    devices = tuple(devices) or None
    with obs.span("sweep", scenarios=len(scenarios)) as root:
        with obs.span("sweep.prepare"):
            prepared = [_prepare(sc) for sc in scenarios]
            # Merge across dataset counts: pad each scenario to the widest
            # partition axis among the scenarios it could share an
            # executable with.  A few dead zero-byte lanes collapse the
            # executable count, and compile time dominates a cold sweep;
            # scenarios whose groups can never merge are left unpadded.
            merged = _merged_partition_counts([p.key for p in prepared])
            prepared = [_pad_partitions(p, merged[p.key]) for p in prepared]
            groups: dict[_GroupKey, list[int]] = defaultdict(list)
            for i, prep in enumerate(prepared):
                groups[prep.key].append(i)
        root.annotate(groups=len(groups))

        results: list[Optional[TransferResult]] = [None] * len(prepared)
        for key, idxs in groups.items():
            with obs.span("sweep.group", lanes=len(idxs),
                          n_steps=key.n_steps,
                          partitions=key.n_partitions):
                with obs.span("sweep.launch"):
                    stacked = jax.tree.map(
                        lambda *xs: np.stack(xs),
                        *[prepared[i].inputs for i in idxs])
                sim_np, done_np = _run_group(key, stacked, len(idxs),
                                             devices)
                with obs.span("sweep.postprocess"):
                    for b, i in enumerate(idxs):
                        results[i] = _postprocess(
                            jax.tree.map(lambda x: x[b], sim_np),
                            done_np[b], prepared[i])
    return results


def group_count(scenarios: Sequence[Scenario]) -> int:
    """Number of compiled executables a ``sweep`` over these would need.

    Computes only the group keys — no controller ``init`` or input-array
    construction — so it is cheap to call before a sweep.  Assumes the
    controller preserves the partition count (all built-in controllers do;
    Algorithm-1 chunking splits files *within* partitions, never partitions).
    Mirrors ``sweep``'s partition padding: scenarios are counted at the
    maximum partition count among the scenarios they could share an
    executable with (same key modulo partition count).
    """
    keys = [_group_key(as_controller(sc.controller),
                       as_environment(sc.environment), sc,
                       len(sc.datasets))
            for sc in scenarios]
    merged = _merged_partition_counts(keys)
    return len({k._replace(n_partitions=merged[k]) for k in keys})
