"""The fleet loop: an arrival stream against a host pool, in waves.

``run_fleet_online`` is the one fleet loop.  It consumes a possibly
unbounded stream with bounded host memory; :func:`run_fleet` runs the same
loop on a finite trace with every bound set so that it never binds.  One
iteration per wave of ``wave_s`` simulated seconds:

1. **Ingest.**  Arrivals come from a generator (``repro.fleet.arrivals``
   stream adapters: Poisson, diurnal, replay) consumed lazily through a
   one-item peek buffer; nothing is materialized.  A queue-depth watermark
   pair applies backpressure: ingest pauses when the waiting queue reaches
   ``queue_high`` and resumes at ``queue_low``.
2. **Faults.**  A ``repro.workloads.faults.FaultSchedule`` kills lanes on
   downed hosts and named victims (in name order) and requeues what
   remains through ``admission.resume_request``.
3. **Admit.**  Waiting requests are assigned hosts FIFO by
   ``admission.pick_host``, then claim the lowest free slot in their
   group's :class:`repro.fleet.ringbuf.SlotPool` — one pool per
   (controller code, environment code, cpu, stride) group, so a
   heterogeneous host pool compiles one wave runner per distinct physics.
   Pool full ⇒ the request waits.  Slot indices are a pure function of the
   arrival prefix, so every host of a multi-host deployment reproduces the
   same layout from the broadcast stream.
4. **Rescale.**  Per host, when the per-flow demands of its in-flight
   transfers exceed the NIC (capped by any fault-injected degrade window),
   every transfer on it gets its bandwidth scaled by ``nic / demand`` for
   the coming wave (``admission.nic_shares``).
5. **Run.**  Each occupied pool advances its first ``SlotPool.width()``
   rows one wave through the jitted wave runner
   (``engine.get_wave_runner``): the width follows the highest occupied
   slot in powers of two, so a group compiles O(log capacity) programs.
   The wave is sharded over the devices when each gets a lane
   (``sharding.should_shard``), the width then rounded up to a multiple
   of the device count.
6. **Retire & fold.**  Drained (or budget-exhausted) slots produce their
   ``admission.make_transfer`` record, folded at once into
   :class:`repro.fleet.aggregates.FleetFold` — exact streaming totals
   (order-independent Shewchuk summation) and DDSketch percentiles with a
   documented relative-error bound — and the slot is freed.  On stream
   end the loop drains: ingest stops, waves continue until the last lane
   retires.

The wave runner shares the engine's per-tick step function and completion
masking, so a transfer that never sees contention is **bit-identical** to
an independent ``api.run`` of the same scenario, at any wave width (tested
in tests/test_fleet.py and tests/test_fleet_online.py).  Host memory is a
function of ``pool_capacity`` + ``queue_high``, never of stream length —
the 1M-transfer diurnal benchmark runs at the same peak RSS as a 100k run
(benchmarks/fleet.py ``--online``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Iterable, Iterator, Optional, Sequence

import jax
import numpy as np

from repro.api.controllers import as_controller
from repro.core import engine, tickstate
from repro.distributed import sharding as shd

from .admission import (Combo, budget_steps, combo_key, make_transfer,
                        nic_shares, pick_host, resume_request)
from .aggregates import FleetFold, FleetReport, HostStats, OnlineFleetReport
from .arrivals import TransferRequest, replay_stream, request_sort_key
from .hosts import Host
from .ringbuf import SlotPool, wave_width


@dataclasses.dataclass(frozen=True)
class OnlineConfig:
    """Knobs for :func:`run_fleet_online` (Alpa-style options object).

    * ``wave_s`` / ``dt`` — the scheduling quantum and the engine tick.
      Admissions and bandwidth rescaling happen at wave boundaries, so a
      transfer's ``total_s`` budget is quantized up to whole waves.
    * ``pool_capacity`` — max in-flight lanes **per wave-runner group**
      (per unique controller x environment x cpu x stride).  This, not the
      stream length, bounds slot-pool memory; a full pool queues further
      admissions.  It does not set the wave width, which follows the
      occupancy (see the module docstring).
    * ``max_partitions`` — static ``TickLayout`` width every lane is
      padded to (padding partitions are a bit-exact no-op).  A request
      whose datasets need more partitions than this raises at admission;
      raise the knob to match the workload's widest dataset tuple.
    * ``queue_high`` / ``queue_low`` — ingest backpressure watermarks on
      the waiting queue (pause at high, resume at low).  Bounds queue
      memory; note a paused ingest *delays* arrivals.
    * ``assignment`` — host policy: ``least-loaded`` or ``round-robin``
      (requests pinned to a host ignore it).
    * ``executor`` — the engine lowering of the wave runners:
      ``reference`` or ``blocked`` (bit-identical; ``auto`` is
      ``blocked``).  ``pallas`` raises: the fused kernel has no wave form.
    * ``mesh`` — the devices waves may shard over: a
      :class:`repro.distributed.sharding.MeshConfig`, or a sequence of
      devices (``None``: the default device only).
    * ``horizon_s`` — hard stop for the simulation clock (the way to bound
      a run on a never-ending stream); in-flight lanes retire incomplete,
      queued requests count as ``dropped``.
    * ``track_transfers`` — retain every per-transfer record (O(n) memory)
      on the report, sorted by ``(start_s, name)``.
    * ``rel_err`` — the streaming quantile sketch's relative-error bound
      (documented tolerance on p50/p95/p99 against exact percentiles).
    * ``on_wave`` — optional callable receiving a per-wave counters dict
      (queue depth, in-flight, lanes run, admit/retire counts, recycled
      slots) for live observability; totals/peaks land in the report's
      ``counters`` payload regardless.
    * ``faults`` — a :class:`repro.workloads.faults.FaultSchedule` (host
      loss / NIC degradation / transfer kills) applied between waves,
      with killed transfers requeued through ``resume_request``; adds a
      ``churn`` block to the report.
    * ``slo_s`` — per-request latency SLO: arms the fold's latency sketch
      and violation counter (``latency`` + ``slo`` summary blocks).
    """

    wave_s: float = 30.0
    dt: float = 0.1
    pool_capacity: int = 256
    max_partitions: int = 8
    queue_high: int = 10_000
    queue_low: int = 1_000
    assignment: str = "least-loaded"
    executor: str = "auto"
    mesh: "shd.MeshConfig | Sequence | None" = None
    horizon_s: Optional[float] = None
    track_transfers: bool = False
    rel_err: float = 0.01
    on_wave: Optional[Callable] = None
    faults: Optional[object] = None
    slo_s: Optional[float] = None

    def __post_init__(self):
        if self.pool_capacity < 1:
            raise ValueError(f"pool_capacity must be >= 1, got "
                             f"{self.pool_capacity}")
        if self.max_partitions < 1:
            raise ValueError(f"max_partitions must be >= 1, got "
                             f"{self.max_partitions}")
        if not 0 <= self.queue_low <= self.queue_high:
            raise ValueError(f"need 0 <= queue_low <= queue_high, got "
                             f"low={self.queue_low} high={self.queue_high}")


class _Peek:
    """One-item peek buffer over a request iterator (for idle
    fast-forward: the loop needs the next arrival time without consuming
    it)."""

    __slots__ = ("_it", "_buf", "_done")

    def __init__(self, it: Iterator[TransferRequest]):
        self._it = it
        self._buf = None
        self._done = False

    def peek(self) -> Optional[TransferRequest]:
        if self._buf is None and not self._done:
            self._buf = next(self._it, None)
            if self._buf is None:
                self._done = True
        return self._buf

    def pop(self) -> TransferRequest:
        req = self.peek()
        if req is None:
            raise StopIteration
        self._buf = None
        return req


def _run_wave(pool: SlotPool, key, act: np.ndarray, devices: tuple,
              executor: str, wave_steps: int, dt: float) -> int:
    """Advance the occupied slots ``act`` of ``pool`` one wave, in place;
    returns the number of lanes the wave ran.

    On ``blocked`` the batch is the pool's flat rows as they stand; the
    ``reference`` executor is the parity path — it unpacks the rows into
    the pytree wave contract (per-lane inputs stacked from the slots'
    combos, zeroed for free slots) and repacks the live lanes afterwards,
    bit-identical by construction.
    """
    code, env_code, cpu, ctrl_every = key
    lay = pool.layout
    sharded = shd.should_shard(len(act), devices)
    w = wave_width(int(act[-1]) + 1, len(devices) if sharded else 1)
    step0 = pool.steps_done[:w]
    if executor == "blocked":
        batch = (pool.params[:w], pool.bw[:w], pool.f32[:w], pool.i32[:w],
                 step0)
    else:
        zero = jax.tree.map(np.zeros_like, pool.combos[act[0]].inputs)
        sim, ts = lay.unpack_state(pool.f32[:w], pool.i32[:w])
        inputs = [(c.inputs if c is not None else zero)._replace(bw=b)
                  for c, b in zip(pool.combos[:w], pool.bw[:w])]
        batch = (jax.tree.map(lambda *xs: np.stack(xs), *inputs),
                 sim, ts, step0)
    n_parts = lay.n_partitions if executor == "blocked" else None
    if sharded:
        runner = engine.get_sharded_wave_runner(
            code, env_code, cpu, wave_steps, dt, ctrl_every, devices,
            executor=executor, n_partitions=n_parts)
        out = runner(*shd.shard_batch(batch, shd.batch_mesh(devices)))
    else:
        runner = engine.get_wave_runner(
            code, env_code, cpu, wave_steps, dt, ctrl_every,
            executor=executor, n_partitions=n_parts, donate=True)
        out = runner(*batch)
    if executor == "blocked":
        f32, i32, done_at = out
        pool.f32[:w] = np.asarray(f32)
        pool.i32[:w] = np.asarray(i32)
    else:
        sim, ts, done_at = jax.tree.map(np.asarray, out)
        for s in act:
            pool.f32[s], pool.i32[s] = lay.pack_state(
                jax.tree.map(lambda x: x[s], sim),
                jax.tree.map(lambda x: x[s], ts), xp=np)
    pool.steps_done[act] += wave_steps
    fresh = act[pool.done_at[act] < 0]
    pool.done_at[fresh] = np.asarray(done_at)[fresh]
    return w


def run_fleet_online(stream: Iterable[TransferRequest],
                     hosts: Sequence[Host], *,
                     config: Optional[OnlineConfig] = None,
                     **overrides) -> OnlineFleetReport:
    """Run an arrival stream against a host pool with bounded memory.

    ``stream`` is any iterable of :class:`TransferRequest` in nondecreasing
    arrival order — the ``repro.fleet.arrivals`` stream adapters, or a
    finite trace (validated through ``replay_stream`` either way).  Knobs
    come from ``config`` (an :class:`OnlineConfig`), with keyword
    ``overrides`` applied on top::

        report = run_fleet_online(
            diurnal_stream(base_rate_per_s=2.0, peak_rate_per_s=20.0,
                           period_s=86_400.0, datasets=menu,
                           controllers=("eemt", "me"), profile=CHAMELEON),
            host_pool(8), horizon_s=7 * 86_400.0, pool_capacity=512)

    Returns an :class:`repro.fleet.aggregates.OnlineFleetReport`; see the
    module docstring for the loop and its memory contract.
    """
    cfg = config or OnlineConfig()
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    hosts = tuple(hosts)
    if not hosts:
        raise ValueError("need at least one host")
    wave_steps = int(round(cfg.wave_s / cfg.dt))
    if wave_steps < 1:
        raise ValueError(f"wave_s={cfg.wave_s} shorter than dt={cfg.dt}")
    executor = engine.resolve_executor(cfg.executor)
    if executor not in ("reference", "blocked"):
        raise ValueError(f"fleet waves run on executor 'reference' or "
                         f"'blocked' (or 'auto'), not {executor!r}")
    dt, wave_s = cfg.dt, cfg.wave_s
    devices = tuple(cfg.mesh.devices()
                    if isinstance(cfg.mesh, shd.MeshConfig)
                    else cfg.mesh or ())

    lay = tickstate.TickLayout(cfg.max_partitions)
    combos: dict[tuple, Combo] = {}

    def combo_for(req: TransferRequest, host: Host) -> Combo:
        ck = combo_key(req, host)
        c = combos.get(ck)
        if c is None:
            c = Combo(req, host, dt)
            if c.n_partitions > cfg.max_partitions:
                raise ValueError(
                    f"request {req.name!r} needs {c.n_partitions} "
                    f"partitions but OnlineConfig.max_partitions="
                    f"{cfg.max_partitions}; raise the knob to the "
                    f"workload's widest dataset tuple")
            c.finalize(cfg.max_partitions)
            combos[ck] = c
        return c

    pools: dict[tuple, SlotPool] = {}
    fold = FleetFold(rel_err=cfg.rel_err, slo_s=cfg.slo_s)
    tracked: Optional[list] = [] if cfg.track_transfers else None
    faults = cfg.faults
    churn = faults.churn_fold() if faults is not None else None
    last_fault_s = -math.inf

    active = [0] * len(hosts)
    busy_waves = [0] * len(hosts)
    moved_mb = [0.0] * len(hosts)
    peak = [0] * len(hosts)
    rr = [0]
    seq = 0
    wave = 0
    waves_run = 0
    paused = False
    waiting: list[TransferRequest] = []
    ingested = 0
    admitted_total = 0
    retired_total = 0
    lanes_total = 0
    peak_queue = 0
    peak_in_flight = 0
    paused_waves = 0

    src = _Peek(iter(replay_stream(stream)))

    def fold_transfer(pool: SlotPool, slot: int) -> None:
        h = int(pool.host_idx[slot])
        name = pool.names[slot]
        t = make_transfer(
            lay, pool.f32[slot],
            name=name,
            controller=pool.ctrl_names[slot],
            host=hosts[h].name,
            arrival_s=float(pool.arrival_s[slot]),
            start_s=float(pool.start_s[slot]),
            steps_done=int(pool.steps_done[slot]),
            done_at=int(pool.done_at[slot]),
            dt=dt,
            ideal_s=float(pool.ideal_s[slot]),
        )
        fold.add(t)
        if churn is not None:
            churn.retire(name, attempt=pool.reqs[slot].attempt,
                         completed=t.completed,
                         offered_parts=pool.combos[slot].offered_parts,
                         remaining_parts=pool.f32[slot, :lay.n_partitions],
                         energy_j=t.energy_j)
        if tracked is not None:
            tracked.append(t)
        active[h] -= 1

    while True:
        now = wave * wave_s
        if cfg.horizon_s is not None and now >= cfg.horizon_s:
            break

        # -- ingest (backpressured) ----------------------------------- --
        if paused and len(waiting) <= cfg.queue_low:
            paused = False
        if paused:
            paused_waves += 1
        while not paused:
            nxt = src.peek()
            if nxt is None or nxt.arrival_s > now:
                break
            waiting.append(src.pop())
            ingested += 1
            if len(waiting) >= cfg.queue_high:
                paused = True
        peak_queue = max(peak_queue, len(waiting))

        # -- faults (victims in name order, requeued at the tail) ------ --
        down = frozenset()
        if faults is not None:
            down = faults.down_hosts(now, now + wave_s)
            kill_names = faults.kills_in(last_fault_s, now)
            last_fault_s = now
            victims = []
            for pool in pools.values():
                for slot in pool.active_slots():
                    slot = int(slot)
                    h = int(pool.host_idx[slot])
                    name = pool.names[slot]
                    if h in down:
                        victims.append((name, "host", pool, slot))
                    elif name in kill_names:
                        victims.append((name, "kill", pool, slot))
            victims.sort(key=lambda v: v[0])
            for name, kind, pool, slot in victims:
                req = pool.reqs[slot]
                combo = pool.combos[slot]
                rem = pool.f32[slot, :lay.n_partitions].copy()
                requeue = resume_request(req, name, combo.specs, rem,
                                         restart=faults.restart)
                churn.kill(name, kind=kind, attempt=req.attempt,
                           offered_parts=combo.offered_parts,
                           remaining_parts=rem,
                           energy_j=float(lay.energy_j(pool.f32[slot])),
                           requeued=requeue is not None)
                if requeue is not None:
                    waiting.append(requeue)
                active[int(pool.host_idx[slot])] -= 1
                pool.release(slot)

        # -- admit (FIFO, shared policy, slot from the group's pool) -- --
        admitted = 0
        still = []
        for req in waiting:
            h = pick_host(req, hosts, active, cfg.assignment, rr, down)
            if h is None:
                still.append(req)
                continue
            combo = combo_for(req, hosts[h])
            pool = pools.get(combo.key)
            if pool is None:
                pool = pools[combo.key] = SlotPool(
                    cfg.pool_capacity, lay, len(devices) or 1)
            slot = pool.alloc()
            if slot is None:              # group pool full: keep waiting
                still.append(req)
                continue
            pool.params[slot] = combo.params_row
            pool.f32[slot] = combo.f0
            pool.i32[slot] = combo.i0
            pool.budget[slot] = budget_steps(req, dt)
            pool.host_idx[slot] = h
            pool.start_s[slot] = now
            pool.arrival_s[slot] = req.arrival_s
            pool.ideal_s[slot] = combo.ideal_s
            pool.demand_mbps[slot] = req.profile.bandwidth_mbps
            pool.names[slot] = req.name or f"xfer-{seq}"
            pool.ctrl_names[slot] = combo.ctrl_name
            pool.reqs[slot] = req
            pool.combos[slot] = combo
            seq += 1
            admitted += 1
            active[h] += 1
            peak[h] = max(peak[h], active[h])
        waiting = still
        admitted_total += admitted

        in_flight = sum(p.in_flight for p in pools.values())
        peak_in_flight = max(peak_in_flight, in_flight)
        if in_flight == 0:
            nxt = src.peek()
            if nxt is None and not waiting:
                break                      # drained: stream + queue empty
            if not waiting:
                # Idle gap: jump straight to the wave of the next arrival.
                wave = max(wave + 1,
                           int(math.ceil(nxt.arrival_s / wave_s)))
                continue
            # Queued but nothing admissible (fault-downed hosts, or a
            # request pinned to one): step wave by wave until a host
            # returns.
            wave += 1
            continue

        # -- rescale (shared NIC-share policy) ------------------------- --
        demand = [0.0] * len(hosts)
        for pool in pools.values():
            for slot in pool.active_slots():
                demand[int(pool.host_idx[slot])] += float(
                    pool.demand_mbps[slot])
        caps = (faults.nic_caps(hosts, now, now + wave_s)
                if faults is not None else None)
        share = np.asarray(nic_shares(hosts, demand, caps), np.float32)

        # -- run one wave per occupied pool (occupancy-wide batches) --- --
        retired = 0
        lanes = 0
        hosts_active = set()
        for key, pool in pools.items():
            if pool.in_flight == 0:
                continue
            act = pool.active_slots()
            np.put(pool.bw, act, share[pool.host_idx[act]])
            before = pool.f32[act, lay.off_bytes].copy()
            lanes += _run_wave(pool, key, act, devices, executor,
                               wave_steps, dt)

            for slot, b in zip(act, before):
                h = int(pool.host_idx[slot])
                moved_mb[h] += float(pool.f32[slot, lay.off_bytes]) - float(b)
                hosts_active.add(h)
            rem = pool.f32[act, :lay.n_partitions].sum(axis=1)
            exhausted = pool.steps_done[act] >= pool.budget[act]
            for slot in act[(rem <= 0.0) | exhausted]:
                fold_transfer(pool, int(slot))
                pool.release(int(slot))
                retired += 1
        retired_total += retired
        lanes_total += lanes
        for h in hosts_active:
            busy_waves[h] += 1
        waves_run += 1

        if cfg.on_wave is not None:
            cfg.on_wave({
                "wave": wave, "now": now, "queue_depth": len(waiting),
                "in_flight": in_flight, "lanes": lanes,
                "admitted": admitted, "retired": retired,
                "ingest_paused": paused,
                "recycled": sum(p.recycled for p in pools.values()),
            })
        wave += 1

    # Horizon cut (or pool drain on break): in-flight lanes retire
    # incomplete.
    for pool in pools.values():
        for slot in pool.active_slots():
            fold_transfer(pool, int(slot))
            pool.release(int(slot))
    dropped = len(waiting)
    if churn is not None:
        churn.finalize()

    if tracked is not None:
        tracked.sort(key=lambda t: (t.start_s, t.name))

    counters = {
        "ingested": ingested,
        "admitted": admitted_total,
        "retired": retired_total,
        "recycled_slots": sum(p.recycled for p in pools.values()),
        "peak_queue_depth": peak_queue,
        "peak_in_flight": peak_in_flight,
        "peak_pool_in_flight": max(
            (p.peak_in_flight for p in pools.values()), default=0),
        "ingest_paused_waves": paused_waves,
        "pools": len(pools),
        "pool_capacity": cfg.pool_capacity,
        "waves_run": waves_run,
        "lanes_run": lanes_total,
        "admit_rate_per_wave": admitted_total / max(waves_run, 1),
        "retire_rate_per_wave": retired_total / max(waves_run, 1),
    }
    # busy_frac is over ALL simulated waves (the final `wave` spans sim_s,
    # including the idle gaps the loop fast-forwarded past); waves_run
    # counts only waves actually executed.
    stats = tuple(
        HostStats(
            name=h.name,
            moved_mb=float(moved_mb[i]),
            busy_frac=busy_waves[i] / max(wave, 1),
            nic_util=(moved_mb[i]
                      / max(h.nic_mbps * busy_waves[i] * wave_s, 1e-9)),
            peak_active=peak[i],
        )
        for i, h in enumerate(hosts))
    return OnlineFleetReport(
        fold=fold, host_stats=stats, sim_s=wave * wave_s, waves=waves_run,
        wave_s=wave_s, dt=dt, dropped=dropped, counters=counters,
        transfers=tuple(tracked) if tracked is not None else None,
        churn=churn.report() if churn is not None else None)


def run_fleet(trace: Sequence[TransferRequest], hosts: Sequence[Host], *,
              wave_s: float = 30.0, dt: float = 0.1,
              horizon_s: Optional[float] = None,
              assignment: str = "least-loaded",
              devices: Optional[Sequence] = None,
              executor: str = "auto",
              faults=None,
              slo_s: Optional[float] = None) -> FleetReport:
    """Run a finite arrival trace against a host pool: the fleet loop of
    :func:`run_fleet_online` with no bound that could bind, keeping every
    per-transfer record.

    The trace is sorted by ``request_sort_key`` first, so every scheduling
    decision is a function of (arrival time, request content), never of
    trace order: shuffling a trace leaves every number unchanged.
    ``horizon_s`` hard-stops the simulation; by default the fleet runs
    until every transfer completes or exhausts its budget, and requests
    never admitted count as ``dropped``.  ``devices`` selects the devices
    waves may shard over (default: all local devices; a wave shards only
    where each device gets a lane).  ``wave_s``, ``dt``, ``assignment``,
    ``executor``, ``faults`` and ``slo_s`` mean what they do on
    :class:`OnlineConfig`; without faults or an SLO the report carries
    neither a ``churn`` nor a ``latency``/``slo`` block.
    """
    hosts = tuple(hosts)
    if not hosts:
        raise ValueError("need at least one host")
    reqs = sorted(trace, key=request_sort_key)

    # The trace-wide partition maximum makes every lane shape-compatible.
    # It is a function of the datasets alone (Algorithm-1 chunking splits
    # files *within* partitions), so the pinned (or first) host's
    # controller init covers whichever host the request lands on.
    firsts = {}
    for req in reqs:
        if req.host is not None and not 0 <= req.host < len(hosts):
            raise ValueError(f"request {req.name!r} pinned to host "
                             f"{req.host}, pool has {len(hosts)}")
        host = hosts[req.host if req.host is not None else 0]
        firsts.setdefault(combo_key(req, host), (req, host))
    p_max = max((len(as_controller(r.controller).init(
                     r.datasets, r.profile, h.cpu).specs)
                 for r, h in firsts.values()), default=1)
    # Each request holds at most one slot at a time (a kill frees its slot
    # before the requeue is admitted), and budgeted hosts cap the total.
    capacity = max(len(reqs), 1)
    if all(h.slots > 0 for h in hosts):
        capacity = min(capacity, sum(h.slots for h in hosts))
    rep = run_fleet_online(
        reqs, hosts, wave_s=wave_s, dt=dt, horizon_s=horizon_s,
        assignment=assignment, executor=executor, faults=faults,
        slo_s=slo_s, pool_capacity=capacity, max_partitions=p_max,
        queue_high=len(reqs) + 1, queue_low=len(reqs) + 1,
        mesh=tuple(jax.devices() if devices is None else devices),
        track_transfers=True)
    return FleetReport(
        transfers=rep.transfers, host_stats=rep.host_stats, sim_s=rep.sim_s,
        waves=rep.waves, wave_s=wave_s, dt=dt,
        dropped=rep.dropped + len(reqs) - rep.counters["ingested"],
        slo_s=slo_s, churn=rep.churn)
