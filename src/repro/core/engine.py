"""Transfer engine: a lowered tick core with pluggable executors.

The engine is a *substrate*: it composes any ``repro.api`` Environment
(a NetworkModel + EnergyModel pair — the physics) with any object
implementing the ``repro.api`` Controller protocol (the algorithm).  All
controller-specific semantics — which channels each partition gets, what
happens on a controller tick, whether frequency/core scaling is active —
live behind the Controller protocol; all physics — per-tick network
behaviour, CPU capacity, power draw — behind the Environment protocol.
The engine itself only drives the clock: it imports neither
``network_model`` nor ``energy_model``.

How simulation time works
-------------------------
A transfer gets a padded horizon of ``n_steps`` ticks of ``dt`` seconds, but
is only *simulated* until it drains:

* **Completion masking.**  Every tick computes a ``live`` flag (the transfer
  still has bytes remaining and the tick is inside the horizon).  Once the
  last partition drains, the whole simulation state — ``energy_j``, ``t``,
  ``window_mb``, the controller accumulators — freezes at its completion
  value, and all emitted per-tick metrics are masked to zero.  Energy is
  therefore integrated over the *transfer*, not over the padded horizon:
  results are invariant to how generous ``total_s`` was.
* **Chunked early exit.**  The horizon is split into fixed-size chunks; an
  outer ``lax.while_loop`` runs one ``lax.scan`` per chunk and stops as soon
  as every lane of the (possibly vmapped) batch reports done.  A transfer
  finishing in 300 s of a 3600 s horizon costs ~1 chunk past completion
  instead of the full padded scan.  ``early_exit=False`` builds the
  reference full-horizon scan; both paths share one step function and are
  bit-identical (see tests/test_engine_properties.py).
* **Done semantics.**  ``TickMetrics.done[i]`` is recorded *after* step
  ``i``: it is True from the tick during which the transfer drained.  The
  completion time is therefore ``(argmax(done) + 1) * dt``, and ``SimState.t``
  freezes at exactly that value.  Cores built with ``traces=False`` (what
  ``repro.api.sweep`` runs) emit no per-tick metrics: they carry that index
  as ``done_at`` (int32, -1 while the transfer is live) through the loop.

The lowering contract (flat state + executors)
----------------------------------------------
Engine semantics are *defined* on nested pytree carries — ``(SimState,
TunerState)`` — by :func:`make_step_fn`, because that is the shape the
Controller/Environment protocols speak.  Execution, however, is pluggable.
An **executor** decides how those semantics are driven:

* ``reference`` — the chunked early-exit ``lax.scan`` over the pytree
  carry, exactly as above.  This is the golden-tested baseline every other
  executor must reproduce bit-for-bit.
* ``blocked`` — a hand-blocked scan whose loop-boundary carries are the
  flat structure-of-arrays ``TickState`` rows of
  :class:`repro.core.tickstate.TickLayout` (one f32 row of ``2P + 9``
  slots, one i32 row of 3).  The per-tick network advance routes through
  the array-form ``step_arrays`` lowering (native when the model provides
  one, otherwise derived from the pytree ``step`` via the bit-exact
  pack/unpack adapters).  The fleet wave runner additionally takes whole
  lane batches as stacked rows — donated on the sharded path — so a wave
  is a handful of ``np.stack`` calls instead of per-lane pytree traffic.
* ``pallas`` — a fused network-step + energy-model + controller-FSM tick
  kernel (one ``pallas_call`` per transfer, per-tick metrics stored from
  inside the kernel).  It runs in Pallas interpret mode only: the TPU
  compiler refuses it (the packed parameter row is not a lane-aligned
  block under ``vmap``, and the unbatched kernel uses ``dynamic_slice``),
  so an explicit ``pallas`` request on a TPU backend raises at resolution
  (ROADMAP A2).  ``observe=True`` is not supported here either.

``executor="auto"`` resolves to ``blocked`` on every backend
(:func:`resolve_executor`).  Because the pack/unpack adapters are pure
concatenation/slicing, every executor is bit-identical on the golden
run/sweep/fleet cells (tests/test_executors.py).  The wave and sharded
runners speak ``reference`` and ``blocked`` only; an explicit ``pallas``
request there raises instead of being swapped for another executor.

Everything numeric (testbed profile, SLA hyper-parameters, dataset sizes,
initial operating point, bandwidth schedule) arrives as traced ``ScanInputs``
leaves, so a whole grid of scenarios that share one controller + environment
code path runs as a single ``jax.vmap``-over-scan XLA launch — see
``repro.api.sweep``, which additionally shards large groups across devices.
Runners are built once per (controller code, environment code, cpu, n_steps,
dt, ctrl_every, executor) group and kept in explicit caches —
:func:`clear_runner_caches` drops them (test fixtures call it so repeated
sweeps in one process don't accumulate compiled executables without bound).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import tickstate
from . import tuners
from .types import (CpuProfile, NetParams, SLAParams, TickMetrics,
                    TransferParams, TunerState, partition_sum)

# Chunking of the early-exit loop.  Purely a performance knob (completion
# masking keeps any chunking bit-identical): larger chunks amortize the
# while-loop overhead — XLA compile time and the vmapped-while carry
# masking both scale with the chunk COUNT, measured ~6x on a 288k-tick
# horizon at 563 chunks vs 64 — while smaller chunks exit closer to the
# actual completion tick.  The default bounds the count at MAX_CHUNKS
# (overshoot <= n_steps / MAX_CHUNKS ticks, ~1.6% of the horizon).
MIN_CHUNK = 512
MAX_CHUNKS = 64

#: Executor names accepted everywhere an ``executor=`` knob exists
#: (plus "auto", which is "blocked").
EXECUTORS = ("reference", "blocked", "pallas")


def resolve_executor(executor: str = "auto", *, observe: bool = False,
                     backend: Optional[str] = None) -> str:
    """Resolve an executor request to a concrete executor name.

    ``auto`` is ``blocked`` on every backend.  Explicit names pass through
    after validation, except where they cannot run, which is rejected here
    at the resolution boundary instead of deep inside a trace or compile:
    ``pallas`` with ``observe=True`` (the fused kernel emits no Observation
    traces), and ``pallas`` on a ``tpu`` backend (the TPU compiler refuses
    the kernel; see ROADMAP A2).
    """
    if executor == "auto":
        return "blocked"
    if executor not in EXECUTORS:
        raise ValueError(f"unknown executor {executor!r}; expected one of "
                         f"{('auto',) + EXECUTORS}")
    if executor == "pallas":
        if observe:
            raise ValueError("the pallas executor does not support "
                             "observe=True; use executor='blocked' (or "
                             "'auto')")
        if (backend or jax.default_backend()) == "tpu":
            raise ValueError(
                "the fused pallas tick kernel does not compile for TPU yet "
                "(ROADMAP A2); use executor='blocked' (or 'auto')")
    return executor


@dataclasses.dataclass
class TransferResult:
    """Post-processed outcome of one simulated transfer.

    ``avg_tput_MBps`` is megabytes/second (the engine's internal rate unit);
    ``avg_tput_gbps`` is gigabits/second (the paper's reporting unit).
    """

    name: str
    time_s: float
    energy_j: float
    avg_tput_MBps: float          # MB/s
    avg_tput_gbps: float          # Gbit/s (paper's unit)
    avg_power_w: float
    completed: bool
    # Per-tick traces (numpy) from ``api.run``; ``None`` from ``api.sweep``,
    # whose runners carry only the completion tick.
    metrics: Optional[TickMetrics] = None

    @property
    def avg_tput_mbps(self) -> float:
        raise AttributeError(
            "TransferResult.avg_tput_mbps was removed (the value always held "
            "MB/s, not Mbit/s): use avg_tput_MBps, or avg_tput_gbps for bits")

    def row(self) -> str:
        return (f"{self.name},{self.time_s:.1f},{self.energy_j:.0f},"
                f"{self.avg_tput_gbps:.3f},{self.avg_power_w:.1f}")


class ScanInputs(NamedTuple):
    """Per-scenario numeric inputs to one engine run (a vmap-able pytree)."""

    net: NetParams         # testbed profile scalars
    sla: SLAParams         # tuner hyper-parameter scalars
    pp: jnp.ndarray        # [P] pipelining depth per partition
    par: jnp.ndarray       # [P] parallelism per partition
    total_mb: jnp.ndarray  # [P] partition sizes
    avg_file_mb: jnp.ndarray   # [P] average file (or chunk) size
    state0: TunerState     # initial controller state (numCh, cores, freq, ..)
    static_w: jnp.ndarray  # [P] frozen channel weights (controller-specific)
    bw: jnp.ndarray        # [] or [n_steps] share of the path's bandwidth

    @classmethod
    def from_init(cls, ci, profile) -> "ScanInputs":
        """Assemble inputs from a ``ControllerInit`` + profile, with the
        full path rate: ``bw`` is one float32 1.0 that every tick sees
        (``_replace`` it with an ``[n_steps]`` schedule where one is given;
        :func:`tick_bw` reads either).

        Leaves built here are host-side (numpy) so batch stacking stays on
        the host; ``pp``/``par``/``state0`` pass through as the controller
        produced them: numpy for every built-in controller, and whatever a
        third-party ``init`` returns, which ``_prepare`` normalizes with
        ``np.asarray`` before stacking.
        """
        return cls(
            net=NetParams.from_profile(profile),
            sla=ci.sla,
            pp=ci.params.pp,
            par=ci.params.par,
            total_mb=np.asarray([s.total_mb for s in ci.specs], np.float32),
            avg_file_mb=np.asarray([s.avg_file_mb for s in ci.specs],
                                   np.float32),
            state0=ci.state,
            static_w=np.asarray(ci.static_weights, np.float32),
            bw=np.float32(1.0),
        )


class Observation(NamedTuple):
    """Per-tick rollout capture, emitted only when the engine is built with
    ``observe=True`` (the learned-controller training hook).

    Window quantities (``avg_tput``, ``avg_power``) are computed from the
    controller accumulators with the exact expressions of
    :func:`_controller_tick`, so at controller ticks (``is_ctrl``) they are
    bit-identical to the ``Measurement`` the controller saw.  The operating
    point (``num_ch``/``cores``/``freq_idx``) is recorded *pre-decision* and
    the ``d_*`` fields hold the delta the controller applied this tick
    (zero off controller ticks).  Everything is masked to zero once the
    transfer completes, mirroring ``TickMetrics``.
    """

    avg_tput: jnp.ndarray      # [] f32 MB/s over the accumulation window
    avg_power: jnp.ndarray     # [] f32 W over the accumulation window
    cpu_load: jnp.ndarray      # [] f32 utilisation of the active cores
    remaining_mb: jnp.ndarray  # [] f32 bytes left across partitions
    num_ch: jnp.ndarray        # [] f32 channel budget, pre-decision
    cores: jnp.ndarray         # [] i32 active cores, pre-decision
    freq_idx: jnp.ndarray      # [] i32 frequency index, pre-decision
    bw_scale: jnp.ndarray      # [] f32 contention share of nominal bandwidth
    d_num_ch: jnp.ndarray      # [] f32 channel delta applied this tick
    d_cores: jnp.ndarray       # [] i32 core delta applied this tick
    d_freq_idx: jnp.ndarray    # [] i32 frequency delta applied this tick
    is_ctrl: jnp.ndarray       # [] bool controller ticked (and transfer live)
    live: jnp.ndarray          # [] bool transfer still moving bytes


def _controller_tick(controller, ts: TunerState, sim, load, net, cpu,
                     sla) -> TunerState:
    """Assemble the interval measurement, delegate to the controller, reset
    the accumulators."""
    meas = tuners.Measurement(
        avg_tput=ts.acc_mb / jnp.maximum(ts.acc_s, 1e-6),
        energy_j=ts.acc_j,
        avg_power=ts.acc_j / jnp.maximum(ts.acc_s, 1e-6),
        remaining_mb=partition_sum(sim.remaining_mb),
        cpu_load=load,
        interval_s=ts.acc_s,
    )
    new = controller.tick(ts, meas, net, cpu, sla)
    z = jnp.zeros((), jnp.float32)
    return new._replace(acc_mb=z, acc_j=z, acc_s=z)


class _LoweredEnv:
    """Environment view for the flat executors: the network advance routes
    through the array-form ``step_arrays`` lowering (see
    :class:`repro.core.tickstate.ArrayLoweredNetwork`); the energy model is
    already array-form (scalar operating points) and passes through."""

    __slots__ = ("network", "energy")

    def __init__(self, env, lay: tickstate.TickLayout):
        self.network = tickstate.ArrayLoweredNetwork(env.network, lay)
        self.energy = env.energy


def make_step_fn(controller, env, cpu: CpuProfile, inp: ScanInputs, *,
                 dt: float, ctrl_every: int, n_steps: Optional[int] = None,
                 observe: bool = False):
    """Build the scan step.  ``controller`` supplies the jittable algorithm
    semantics, ``env`` (a ``repro.api`` Environment) the jittable physics;
    static metadata (cpu, dt, ctrl_every) is closed over.

    A tick is ``live`` while the transfer still has bytes remaining *and*
    ``step_idx < n_steps`` (the early-exit loop pads the horizon up to a
    whole number of chunks; padding ticks are frozen no-ops).  Non-live
    ticks freeze the whole carry — including ``energy_j`` and ``t`` — and
    emit zeroed metrics, so post-completion ticks are pure padding.

    With ``observe=True`` the step additionally emits an :class:`Observation`
    per tick (``(metrics, obs)`` instead of ``metrics``) for the
    ``repro.learn`` rollout harness.  The flag is resolved at trace time, so
    the default path compiles to exactly the program it did before the hook
    existed — zero overhead when disabled.
    """

    @jax.named_scope("engine.tick")
    def step(carry, xs):
        sim, ts = carry
        step_idx, bw_scale = xs

        done = partition_sum(sim.remaining_mb) <= 0.0
        if n_steps is not None:
            done = jnp.logical_or(done, step_idx >= n_steps)
        live = jnp.logical_not(done)

        cc = controller.channels(ts, sim, inp.static_w)
        params = TransferParams(pp=inp.pp, par=inp.par, cc=cc,
                                cores=ts.cores, freq_idx=ts.freq_idx)

        sim2, out = env.network.step(env.energy, inp.net, cpu, sim, params,
                                     inp.avg_file_mb, dt, bw_scale)
        # Completion masking: freeze the world (energy, t, windows) once the
        # transfer has completed — the clock only runs while live.
        sim2 = jax.tree.map(lambda new, old: jnp.where(done, old, new),
                            sim2, sim)
        sim2 = sim2._replace(t=sim.t + dt * live)

        ts = ts._replace(
            acc_mb=ts.acc_mb + out.tput_mbps * dt * live,
            acc_j=ts.acc_j + out.power_w * dt * live,
            acc_s=ts.acc_s + dt * live,
        )
        ts_pre = ts  # post-accumulation, pre-decision (what the tick sees)

        if controller.tunes:
            is_ctrl = jnp.logical_and(
                (step_idx % ctrl_every) == ctrl_every - 1, live)
            ts_new = _controller_tick(controller, ts, sim2, out.cpu_load,
                                      inp.net, cpu, inp.sla)
            ts = jax.tree.map(lambda n, o: jnp.where(is_ctrl, n, o),
                              ts_new, ts)
        else:
            is_ctrl = jnp.zeros((), jnp.bool_)

        _, f = env.energy.operating_point(cpu, ts.cores, ts.freq_idx)
        zi = jnp.zeros((), jnp.int32)
        metrics = TickMetrics(
            tput_mbps=out.tput_mbps * live, power_w=out.power_w * live,
            cpu_load=out.cpu_load * live, num_ch=out.num_ch * live,
            cores=jnp.where(live, ts.cores, zi),
            freq_ghz=f * live,
            # Recorded POST-step: True from the tick the transfer drained.
            done=partition_sum(sim2.remaining_mb) <= 0.0,
        )
        if not observe:
            return (sim2, ts), metrics

        win_s = jnp.maximum(ts_pre.acc_s, 1e-6)
        obs = Observation(
            avg_tput=(ts_pre.acc_mb / win_s) * live,
            avg_power=(ts_pre.acc_j / win_s) * live,
            cpu_load=out.cpu_load * live,
            remaining_mb=partition_sum(sim2.remaining_mb) * live,
            num_ch=ts_pre.num_ch * live,
            cores=jnp.where(live, ts_pre.cores, zi),
            freq_idx=jnp.where(live, ts_pre.freq_idx, zi),
            bw_scale=jnp.asarray(bw_scale, jnp.float32) * live,
            d_num_ch=(ts.num_ch - ts_pre.num_ch) * live,
            d_cores=jnp.where(live, ts.cores - ts_pre.cores, zi),
            d_freq_idx=jnp.where(live, ts.freq_idx - ts_pre.freq_idx, zi),
            is_ctrl=is_ctrl,
            live=live,
        )
        return (sim2, ts), (metrics, obs)

    return step


def _init_metrics_buffer(padded: int) -> TickMetrics:
    """Metrics for never-executed ticks: the transfer is long done, so every
    observable is zero and ``done`` is True — exactly what the masked step
    emits for post-completion ticks (keeps early-exit bit-identical to the
    full-horizon scan)."""
    z = jnp.zeros((padded,), jnp.float32)
    return TickMetrics(
        tput_mbps=z, power_w=z, cpu_load=z, num_ch=z,
        cores=jnp.zeros((padded,), jnp.int32),
        freq_ghz=z,
        done=jnp.ones((padded,), jnp.bool_),
    )


def _init_obs_buffer(padded: int) -> Observation:
    """Observations for never-executed ticks: all-zero / not-live, exactly
    what the masked step emits post-completion (keeps ``observe=True``
    early-exit bit-identical to the full-horizon scan)."""
    z = jnp.zeros((padded,), jnp.float32)
    zi = jnp.zeros((padded,), jnp.int32)
    zb = jnp.zeros((padded,), jnp.bool_)
    return Observation(
        avg_tput=z, avg_power=z, cpu_load=z, remaining_mb=z,
        num_ch=z, cores=zi, freq_idx=zi, bw_scale=z,
        d_num_ch=z, d_cores=zi, d_freq_idx=zi,
        is_ctrl=zb, live=zb,
    )


def _done_at(done, step0=0):
    """Completion tick of a ``[steps]`` done trace: ``step0`` plus the first
    tick after which the transfer was drained, or -1 if it never was."""
    return jnp.where(done[-1], step0 + jnp.argmax(done).astype(jnp.int32),
                     jnp.asarray(-1, jnp.int32))


def _track_completion(step):
    """Wrap a scan step to emit nothing and carry ``done_at`` beside the
    state: the first step index after which ``partition_sum(remaining_mb)
    <= 0``, where ``TickMetrics.done`` first turns True."""

    def tracked(carry, xs):
        state, done_at = carry
        state, _ = step(state, xs)
        drained = partition_sum(state[0].remaining_mb) <= 0.0
        done_at = jnp.where(jnp.logical_and(done_at < 0, drained), xs[0],
                            done_at)
        return (state, done_at), None

    return tracked


def tick_bw(bw, horizon: int):
    """One lane's per-tick bandwidth: ``window(start, length)`` gives the
    shares of ticks ``[start, start + length)``.

    ``bw`` is a rank-0 share that every tick sees (a scenario without a
    schedule, a fleet lane's share for one wave) or an ``[n_steps]``
    schedule, padded with zeros to ``horizon`` ticks so that every window
    lies inside it.  Under ``vmap`` the lane's rank is known at trace
    time, so each rank compiles its own program; the tick sees the same
    float32 values either way.
    """
    bw = jnp.asarray(bw, jnp.float32)
    if bw.ndim == 0:
        return lambda start, length: jnp.broadcast_to(bw, (length,))
    bw = jnp.pad(bw, ((0, horizon - bw.shape[0]),))
    return lambda start, length: jax.lax.dynamic_slice(bw, (start,),
                                                       (length,))


def _chunking(n_steps: int, chunk: Optional[int]):
    if chunk is None:
        chunk = max(MIN_CHUNK, -(-n_steps // MAX_CHUNKS))
    chunk = max(min(n_steps, int(chunk)), 1)
    n_chunks = -(-n_steps // chunk)
    return chunk, n_chunks, n_chunks * chunk


def build_core(controller, env, cpu: CpuProfile, *, n_steps: int, dt: float,
               ctrl_every: int, early_exit: bool = True,
               chunk: Optional[int] = None, observe: bool = False,
               traces: bool = True, executor: str = "reference"):
    """One full transfer: ScanInputs -> (final SimState, TunerState, traces).

    Pure and shape-stable in its pytree argument, hence vmap-able across a
    batch of scenarios.  With ``early_exit`` (the default) the horizon is
    split into ``chunk``-tick scans inside a ``lax.while_loop`` that stops
    once every lane of the batch is done; each chunk's metrics land in a
    preallocated [n_steps] buffer (``engine.store``) so the output shape is
    identical to the reference full-horizon scan (``early_exit=False``).

    With ``traces=False`` the third output is ``done_at`` instead of the
    traces: the int32 index of the tick during which the transfer drained
    (``argmax`` of the ``done`` trace), or -1 if it did not.  The early-exit
    loop carries it and its chunk scans emit nothing, so no [n_steps]
    buffer exists; ``observe`` needs the traces.

    ``executor`` selects the lowering (see the module docstring):
    ``reference`` scans the pytree carry, ``blocked`` carries the flat
    ``TickState`` rows across loop boundaries and lowers the network step
    to array form, ``pallas`` fuses the whole tick loop into one kernel
    (``early_exit``/``chunk`` do not apply there — the kernel early-exits
    its internal while loop on completion).

    With ``observe=True`` the core returns ``(sim, ts, metrics, obs)`` where
    ``obs`` is an [n_steps]-shaped :class:`Observation` trace; without it,
    the classic ``(sim, ts, metrics)`` triple (and an unchanged program).
    """
    executor = resolve_executor(executor, observe=observe)
    if observe and not traces:
        raise ValueError("observe=True emits per-tick traces; it needs "
                         "traces=True")
    if executor == "pallas":
        pallas = _build_pallas_core(controller, env, cpu, n_steps=n_steps,
                                    dt=dt, ctrl_every=ctrl_every)
        if traces:
            return pallas

        def trace_free(inp: ScanInputs):
            sim, ts, m = pallas(inp)
            return sim, ts, _done_at(m.done)
        return trace_free
    chunk, n_chunks, padded = _chunking(n_steps, chunk)
    blocked = executor == "blocked"

    def core(inp: ScanInputs):
        n_partitions = int(np.shape(inp.pp)[-1])
        lay = tickstate.TickLayout(n_partitions)
        step_env = _LoweredEnv(env, lay) if blocked else env
        sim0 = env.network.init_state(inp.total_mb, inp.net)
        step = make_step_fn(controller, step_env, cpu, inp, dt=dt,
                            ctrl_every=ctrl_every,
                            n_steps=n_steps if padded != n_steps else None,
                            observe=observe)

        if not early_exit:
            xs = (jnp.arange(n_steps, dtype=jnp.int32),
                  tick_bw(inp.bw, n_steps)(0, n_steps))
            if blocked:
                carry0 = lay.pack_state(sim0, inp.state0)

                def fstep(carry, x):
                    st, ys = step(lay.unpack_state(*carry), x)
                    return lay.pack_state(*st), ys

                (f32, i32), ys = jax.lax.scan(fstep, carry0, xs)
                sim, ts = lay.unpack_state(f32, i32)
            else:
                (sim, ts), ys = jax.lax.scan(step, (sim0, inp.state0), xs)
            if observe:
                return sim, ts, ys[0], ys[1]
            return sim, ts, ys if traces else _done_at(ys.done)

        bw = tick_bw(inp.bw, padded)

        def chunk_xs(start):
            return (start + jnp.arange(chunk, dtype=jnp.int32),
                    bw(start, chunk))

        # ``advance`` runs the chunk that begins at tick ``start``.  ``out``
        # is what the while loop carries beside the state: the [padded]
        # trace buffers, or the completion tick.
        if traces:
            @jax.named_scope("engine.store")
            def store(buf, m, start):
                return jax.tree.map(
                    lambda b, x: jax.lax.dynamic_update_slice(
                        b, x, (start,) + (0,) * (b.ndim - 1)),
                    buf, m)

            def advance(state, buf, start):
                state, m = jax.lax.scan(step, state, chunk_xs(start))
                return state, store(buf, m, start)

            out0 = _init_metrics_buffer(padded)
            if observe:
                out0 = (out0, _init_obs_buffer(padded))
        else:
            tracked = _track_completion(step)

            def advance(state, done_at, start):
                (state, done_at), _ = jax.lax.scan(
                    tracked, (state, done_at), chunk_xs(start))
                return state, done_at

            # A lane born drained reads 0, as its trace does: the loop may
            # never run, and the buffer's never-executed ticks are done.
            out0 = jnp.where(partition_sum(sim0.remaining_mb) <= 0.0,
                             0, -1).astype(jnp.int32)

        if blocked:
            # Flat TickState rows cross the while-loop boundary; the pytree
            # carry lives only inside each chunk's scan.
            def cond(carry):
                k, f32, _, _ = carry
                return jnp.logical_and(
                    k < n_chunks,
                    partition_sum(f32[..., :n_partitions]) > 0.0)

            @jax.named_scope("engine.chunk")
            def body(carry):
                k, f32, i32, out = carry
                st, out = advance(lay.unpack_state(f32, i32), out, k * chunk)
                f32, i32 = lay.pack_state(*st)
                return k + 1, f32, i32, out

            f0, i0 = lay.pack_state(sim0, inp.state0)
            carry0 = (jnp.zeros((), jnp.int32), f0, i0, out0)
            _, f32, i32, out = jax.lax.while_loop(cond, body, carry0)
            sim, ts = lay.unpack_state(f32, i32)
        else:
            def cond(carry):
                k, (sim, _), _ = carry
                return jnp.logical_and(k < n_chunks,
                                       partition_sum(sim.remaining_mb) > 0.0)

            @jax.named_scope("engine.chunk")
            def body(carry):
                k, state, out = carry
                state, out = advance(state, out, k * chunk)
                return k + 1, state, out

            carry0 = (jnp.zeros((), jnp.int32), (sim0, inp.state0), out0)
            _, (sim, ts), out = jax.lax.while_loop(cond, body, carry0)

        if not traces:
            return sim, ts, out
        out = jax.tree.map(lambda b: b[:n_steps], out)
        if observe:
            return sim, ts, out[0], out[1]
        return sim, ts, out

    return core


def _build_pallas_core(controller, env, cpu: CpuProfile, *, n_steps: int,
                       dt: float, ctrl_every: int):
    """Fused tick-loop kernel: one ``pallas_call`` runs the whole transfer.

    Inputs cross the kernel boundary in the flat ``TickState`` form (one
    parameter row, the bandwidth schedule, the packed initial state); the
    kernel reconstructs the traced ``ScanInputs``, drives the *same*
    :func:`make_step_fn` tick — with the network advance lowered to
    ``step_arrays`` form — inside an early-exiting while loop, and stores
    per-tick metrics straight into the output buffers (pre-filled with the
    never-executed-tick values, so the trace is bit-identical to the
    reference scan).  Always runs in interpret mode: the TPU compiler
    refuses this kernel (ROADMAP A2), and :func:`resolve_executor` keeps it
    off TPU backends.
    """
    from jax.experimental import pallas as pl

    def core(inp: ScanInputs):
        n_partitions = int(np.shape(inp.pp)[-1])
        lay = tickstate.TickLayout(n_partitions)
        lowered = _LoweredEnv(env, lay)
        sim0 = env.network.init_state(inp.total_mb, inp.net)
        f0, i0 = lay.pack_state(sim0, inp.state0)
        prow = lay.pack_params(inp)
        bw = tick_bw(inp.bw, n_steps)(0, n_steps)

        # Pallas kernels may not capture non-scalar constants (the CPU
        # frequency/power tables the physics materializes at trace time), so
        # the tick is staged to a jaxpr once against abstract example
        # arguments and its hoisted constants ride into the kernel as extra
        # inputs.
        def tick(kin, carry, xs):
            step = make_step_fn(controller, lowered, cpu, kin, dt=dt,
                                ctrl_every=ctrl_every)
            return step(carry, xs)

        carry_ex = lay.unpack_state(
            jnp.zeros((lay.f32_size,), jnp.float32),
            jnp.zeros((lay.i32_size,), jnp.int32))
        kin_ex = ScanInputs(
            state0=carry_ex[1], bw=jnp.ones((), jnp.float32),
            **lay.unpack_params(jnp.zeros((lay.params_size,), jnp.float32)))
        xs_ex = (jnp.zeros((), jnp.int32), jnp.zeros((), jnp.float32))
        closed = jax.make_jaxpr(tick)(kin_ex, carry_ex, xs_ex)
        consts = [jnp.asarray(c) for c in closed.consts]
        out_tree = jax.tree.structure(
            jax.eval_shape(tick, kin_ex, carry_ex, xs_ex))

        def tick_fn(kin, carry, xs, *cvals):
            flat = jax.tree.leaves((kin, carry, xs))
            out = jax.core.eval_jaxpr(closed.jaxpr, list(cvals), *flat)
            return jax.tree.unflatten(out_tree, out)

        def kernel(prow_ref, bw_ref, f0_ref, i0_ref, *refs):
            const_refs = refs[:len(consts)]
            (fout_ref, iout_ref, tput_ref, power_ref, load_ref, nch_ref,
             cores_ref, freq_ref, done_ref) = refs[len(consts):]
            cvals = [r[:] for r in const_refs]
            fields = lay.unpack_params(prow_ref[:])
            carry = lay.unpack_state(f0_ref[:], i0_ref[:])
            kin = ScanInputs(state0=carry[1],
                             bw=jnp.ones((), jnp.float32), **fields)

            zf = jnp.zeros((n_steps,), jnp.float32)
            for ref in (tput_ref, power_ref, load_ref, nch_ref, freq_ref):
                ref[:] = zf
            cores_ref[:] = jnp.zeros((n_steps,), jnp.int32)
            done_ref[:] = jnp.ones((n_steps,), jnp.int32)

            def cond(c):
                i, (sim, _) = c
                return jnp.logical_and(i < n_steps,
                                       partition_sum(sim.remaining_mb) > 0.0)

            def body(c):
                i, carry = c
                carry, m = tick_fn(kin, carry, (i, bw_ref[i]), *cvals)
                tput_ref[i] = m.tput_mbps
                power_ref[i] = m.power_w
                load_ref[i] = m.cpu_load
                nch_ref[i] = m.num_ch
                cores_ref[i] = m.cores
                freq_ref[i] = m.freq_ghz
                done_ref[i] = m.done.astype(jnp.int32)
                return i + 1, carry

            _, (sim, ts) = jax.lax.while_loop(
                cond, body, (jnp.zeros((), jnp.int32), carry))
            f32, i32 = lay.pack_state(sim, ts)
            fout_ref[:] = f32
            iout_ref[:] = i32

        out_shape = [
            jax.ShapeDtypeStruct((lay.f32_size,), jnp.float32),
            jax.ShapeDtypeStruct((lay.i32_size,), jnp.int32),
        ] + [jax.ShapeDtypeStruct((n_steps,), jnp.float32)] * 4 + [
            jax.ShapeDtypeStruct((n_steps,), jnp.int32),   # cores
            jax.ShapeDtypeStruct((n_steps,), jnp.float32),  # freq_ghz
            jax.ShapeDtypeStruct((n_steps,), jnp.int32),   # done
        ]
        f32, i32, tput, power, load, nch, cores, freq, done = pl.pallas_call(
            kernel, out_shape=out_shape, interpret=True)(
                prow, bw, f0, i0, *consts)
        sim, ts = lay.unpack_state(f32, i32)
        metrics = TickMetrics(tput_mbps=tput, power_w=power, cpu_load=load,
                              num_ch=nch, cores=cores, freq_ghz=freq,
                              done=done.astype(jnp.bool_))
        return sim, ts, metrics

    return core


# ------------------------------------------------------------ caches ------
#
# Compiled runners are cached in explicit per-family dicts keyed on the
# hashable (controller code, env code, cpu, shape..., executor) tuple —
# the same things that select compiled code.  Unlike the old
# functools.lru_cache(maxsize=None) decorators these are inspectable and
# clearable: long-lived processes (pytest sessions, tuning loops) call
# clear_runner_caches() to drop every compiled executable at once.

_CACHES: dict[str, dict] = {
    "runner": {}, "wave": {}, "sharded_wave": {}, "sharded": {},
}


def clear_runner_caches() -> None:
    """Drop every cached compiled runner (figure-grid, wave, and sharded).

    Safe at any time — the next ``get_*_runner`` call rebuilds and
    recompiles.  Test fixtures call this between modules so repeated sweeps
    in one process stop accumulating compiled executables without bound.
    """
    for cache in _CACHES.values():
        cache.clear()


def runner_cache_sizes() -> dict[str, int]:
    """Entries per runner-cache family (observability / leak tests)."""
    return {name: len(cache) for name, cache in _CACHES.items()}


def _cached(family: str, key: tuple, build):
    cache = _CACHES[family]
    if key not in cache:
        cache[key] = build()
    return cache[key]


def get_runner(controller_code, env_code, cpu: CpuProfile, n_steps: int,
               dt: float, ctrl_every: int, batched: bool,
               early_exit: bool = True, chunk: Optional[int] = None,
               observe: bool = False, traces: bool = True,
               executor: str = "auto"):
    """Jitted (and optionally vmapped) engine core, cached per code group.

    ``controller_code`` must be a canonical (numerics-stripped, hashable)
    controller — see ``Controller.code()`` — and ``env_code`` a canonical
    environment (``Environment.code()``).  Scenarios that share a cache key
    share one compiled executable.  When vmapped, the early-exit loop stops
    once *all* lanes of the batch are done (``repro.api.sweep`` keeps groups
    shape-compatible, so lanes tend to finish at similar times).

    ``executor`` is resolved first (:func:`resolve_executor`), so
    ``"auto"`` and ``"blocked"`` share one cache entry.  ``traces`` picks
    the core's form (:func:`build_core`): ``api.run`` keeps the per-tick
    traces, ``api.sweep`` takes the trace-free form.
    """
    executor = resolve_executor(executor, observe=observe)
    key = (controller_code, env_code, cpu, n_steps, dt, ctrl_every,
           batched, early_exit, chunk, observe, traces, executor)

    def build():
        core = build_core(controller_code, env_code, cpu, n_steps=n_steps,
                          dt=dt, ctrl_every=ctrl_every,
                          early_exit=early_exit, chunk=chunk,
                          observe=observe, traces=traces, executor=executor)
        return jax.jit(jax.vmap(core) if batched else core)

    return _cached("runner", key, build)


# ------------------------------------------------------------ wave hooks --
#
# The fleet layer (repro.fleet) runs thousands of concurrent transfers in
# streaming *waves*: each wave advances every active transfer by a fixed
# window of ticks, then the host-side scheduler drains completed lanes,
# refills from the arrival queue, and rescales per-transfer bandwidth for
# NIC contention.  That needs two things the figure-grid runners don't have:
#
#   * resumable carries — a wave starts from the state the previous wave
#     produced, with the global step index threaded through so
#     controller-tick alignment (``step_idx % ctrl_every``) survives wave
#     boundaries;
#   * a scalar per-lane bandwidth share — one float (the host NIC share for
#     this wave), broadcast across the wave's ticks by ``tick_bw``.
#
# Two wave carry forms exist, selected by ``executor``:
#
#   * ``reference`` — pytree carries (``ScanInputs``, SimState, TunerState),
#     exactly the PR 3 contract;
#   * ``blocked`` — flat ``TickState`` rows: the runner takes
#     ``(params_row [B, 13+5P], bw [B], state_f32 [B, 2P+9],
#     state_i32 [B, 3], step0 [B])`` and returns the advanced rows.  A
#     host-side lane is then two ndarray rows, a wave batch is five
#     ``np.stack`` calls, and the sharded runner donates the state buffers.
#
# Both share ``make_step_fn``, so a transfer that never experiences
# contention is bit-identical between the wave path and ``api.run``
# (tests/test_fleet.py, tests/test_executors.py).  Waves return only the
# final carries plus the absolute tick at which the lane drained (-1 if
# still live): per-tick traces would be O(fleet size x horizon) and fleet
# metrics only need completion tick + the frozen energy/bytes counters.


def build_wave_core(controller, env, cpu: CpuProfile, *, wave_steps: int,
                    dt: float, ctrl_every: int):
    """One wave of one transfer: (inputs, carry, step0) -> (carry', done_at).

    ``step0`` is the lane's absolute tick index at wave start (ticks since
    the transfer was admitted); ``done_at`` is the absolute tick during
    which the transfer drained, or -1 if it is still live after the wave.
    Completion masking freezes drained lanes, so running a done lane for
    further waves is a no-op — the scheduler drains them instead.
    """

    def core(inp: ScanInputs, sim0, ts0, step0):
        step = make_step_fn(controller, env, cpu, inp, dt=dt,
                            ctrl_every=ctrl_every)

        def wave_step(carry, xs):
            carry, m = step(carry, xs)
            return carry, m.done

        idx = step0 + jnp.arange(wave_steps, dtype=jnp.int32)
        bw = tick_bw(inp.bw, wave_steps)(0, wave_steps)
        (sim, ts), done = jax.lax.scan(wave_step, (sim0, ts0), (idx, bw))
        return sim, ts, _done_at(done, step0)

    return core


def build_blocked_wave_core(controller, env, cpu: CpuProfile, *,
                            wave_steps: int, dt: float, ctrl_every: int,
                            n_partitions: int):
    """Flat-carry wave core: (params_row, bw, f32, i32, step0) ->
    (f32', i32', done_at).

    The per-lane rows follow :class:`repro.core.tickstate.TickLayout` for
    ``n_partitions``; ``ScanInputs`` is reconstructed from the parameter
    row inside the trace (pure slicing), the tick itself is the shared
    :func:`make_step_fn` with the network advance in ``step_arrays`` form,
    and the advanced state is re-packed on the way out — bit-identical to
    :func:`build_wave_core` by construction.
    """
    lay = tickstate.TickLayout(n_partitions)
    lowered = _LoweredEnv(env, lay)

    def core(params_row, bw, f32, i32, step0):
        fields = lay.unpack_params(params_row)
        sim0, ts0 = lay.unpack_state(f32, i32)
        inp = ScanInputs(state0=ts0, bw=bw, **fields)
        step = make_step_fn(controller, lowered, cpu, inp, dt=dt,
                            ctrl_every=ctrl_every)

        def wave_step(carry, xs):
            carry, m = step(carry, xs)
            return carry, m.done

        idx = step0 + jnp.arange(wave_steps, dtype=jnp.int32)
        bws = tick_bw(bw, wave_steps)(0, wave_steps)
        (sim, ts), done = jax.lax.scan(wave_step, (sim0, ts0), (idx, bws))
        f32_out, i32_out = lay.pack_state(sim, ts)
        return f32_out, i32_out, _done_at(done, step0)

    return core


def _resolve_unfused_executor(executor: str, *, wave: bool = False,
                              n_partitions: Optional[int] = None) -> str:
    """The wave and sharded runners speak ``reference`` and ``blocked``: an
    explicit ``pallas`` request raises (the fused kernel has neither form)
    instead of being swapped for another executor.  A ``blocked`` wave
    runner also needs the static ``n_partitions``."""
    executor = resolve_executor(executor)
    if executor == "pallas":
        raise ValueError("wave and sharded runners support "
                         "executor='reference' or 'blocked' (or 'auto'), "
                         "not 'pallas'")
    if wave and executor == "blocked" and n_partitions is None:
        raise ValueError("blocked wave runners need n_partitions (the "
                         "static TickLayout width)")
    return executor


def get_wave_runner(controller_code, env_code, cpu: CpuProfile,
                    wave_steps: int, dt: float, ctrl_every: int,
                    executor: str = "auto",
                    n_partitions: Optional[int] = None,
                    donate: bool = False):
    """Jitted, vmapped wave core, cached per (controller, environment) code
    group.

    Lanes are independent (no early-exit barrier inside a wave), so padding
    lanes with drained transfers (zero remaining bytes) is free: they are
    frozen from tick 0.  With ``executor="blocked"`` the runner speaks the
    flat-row contract of :func:`build_blocked_wave_core` and needs the
    static ``n_partitions``.

    ``donate=True`` donates the state-carry buffers (the flat f32/i32 rows
    on ``blocked``, the SimState/TunerState pytrees on ``reference``) —
    what the fleet loop's persistent slot pools want: the pool's first
    rows, as many as the wave is wide, flow through every wave, so
    donation makes the wave an in-place update instead of an
    alloc-and-copy.  Callers must
    then treat the passed-in buffers as consumed.  Slot recycling composes
    with the wave contract for free: a retired slot's rows are zeroed
    (born-drained no-op lane) until the next admission overwrites them with
    fresh tick-0 rows and re-enters the wave loop at ``step0 = 0`` —
    ``done_at`` is relative to the *lane's* tick clock, not the fleet's, so
    a recycled slot is indistinguishable from a new lane.
    """
    executor = _resolve_unfused_executor(executor, wave=True,
                                         n_partitions=n_partitions)
    key = (controller_code, env_code, cpu, wave_steps, dt, ctrl_every,
           executor, n_partitions, donate)

    def build():
        if executor == "blocked":
            core = build_blocked_wave_core(
                controller_code, env_code, cpu, wave_steps=wave_steps,
                dt=dt, ctrl_every=ctrl_every, n_partitions=n_partitions)
            donate_argnums = (2, 3)
        else:
            core = build_wave_core(controller_code, env_code, cpu,
                                   wave_steps=wave_steps, dt=dt,
                                   ctrl_every=ctrl_every)
            donate_argnums = (1, 2)
        if donate:
            return jax.jit(jax.vmap(core), donate_argnums=donate_argnums)
        return jax.jit(jax.vmap(core))

    return _cached("wave", key, build)


def get_sharded_wave_runner(controller_code, env_code, cpu: CpuProfile,
                            wave_steps: int, dt: float, ctrl_every: int,
                            devices: tuple, executor: str = "auto",
                            n_partitions: Optional[int] = None):
    """Wave runner sharded over ``devices`` along the lane axis.

    Same contract as :func:`get_wave_runner`; the lane count must be a
    multiple of ``len(devices)`` (the fleet loop rounds a sharded wave's
    width up to one; zeroed lanes are drained no-ops).  The carry buffers are
    donated — each wave consumes the previous wave's output states (the
    flat f32/i32 state rows on the ``blocked`` path, the SimState/TunerState
    pytrees on ``reference``).
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    executor = _resolve_unfused_executor(executor, wave=True,
                                         n_partitions=n_partitions)
    key = (controller_code, env_code, cpu, wave_steps, dt, ctrl_every,
           devices, executor, n_partitions)

    def build():
        mesh = shd.batch_mesh(devices)
        if executor == "blocked":
            core = build_blocked_wave_core(
                controller_code, env_code, cpu, wave_steps=wave_steps,
                dt=dt, ctrl_every=ctrl_every, n_partitions=n_partitions)
            f = jax.shard_map(jax.vmap(core), mesh=mesh,
                              in_specs=(P("batch"),) * 5,
                              out_specs=P("batch"), check_vma=False)
            return jax.jit(f, donate_argnums=(2, 3))
        core = build_wave_core(controller_code, env_code, cpu,
                               wave_steps=wave_steps, dt=dt,
                               ctrl_every=ctrl_every)
        f = jax.shard_map(jax.vmap(core), mesh=mesh,
                          in_specs=(P("batch"),) * 4,
                          out_specs=P("batch"), check_vma=False)
        return jax.jit(f, donate_argnums=(1, 2))

    return _cached("sharded_wave", key, build)


def get_sharded_runner(controller_code, env_code, cpu: CpuProfile,
                       n_steps: int, dt: float, ctrl_every: int,
                       devices: tuple, early_exit: bool = True,
                       chunk: Optional[int] = None,
                       executor: str = "auto"):
    """Batched trace-free engine core (``build_core(traces=False)``: it
    returns ``(sim, ts, done_at)``) sharded over ``devices`` along the batch
    axis.

    Built with ``shard_map`` over a 1-D ``batch`` mesh, so each device runs
    the early-exit loop on its own shard independently — a device whose
    lanes all finish early stops scanning without waiting for the others.
    Input batches must be padded to a multiple of ``len(devices)``
    (``repro.distributed.sharding.pad_batch``) and placed with
    ``shard_batch``; the jit donates the input buffers.  Supports
    ``reference`` and ``blocked``; an explicit ``pallas`` request raises.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    executor = _resolve_unfused_executor(executor)
    key = (controller_code, env_code, cpu, n_steps, dt, ctrl_every,
           devices, early_exit, chunk, executor)

    def build():
        mesh = shd.batch_mesh(devices)
        core = build_core(controller_code, env_code, cpu, n_steps=n_steps,
                          dt=dt, ctrl_every=ctrl_every,
                          early_exit=early_exit, chunk=chunk,
                          traces=False, executor=executor)
        f = jax.shard_map(jax.vmap(core), mesh=mesh, in_specs=(P("batch"),),
                          out_specs=P("batch"), check_vma=False)
        return jax.jit(f, donate_argnums=0)

    return _cached("sharded", key, build)


def __getattr__(name):
    if name == "simulate":
        raise AttributeError(
            "repro.core.engine.simulate was removed: build a "
            "repro.api.Scenario and call repro.api.run (or repro.api.sweep)")
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
