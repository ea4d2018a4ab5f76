"""The fleet loop on streams: parity with api.run, bounded memory,
backpressure.

The load-bearing contracts:

* **Independent parity** — a transfer that never sees contention gives
  the bits of an independent ``api.run`` of the same scenario, while the
  wave width follows the pools' occupancy; the exact streaming totals
  bit-equal ``math.fsum`` over the per-transfer records.  Only the
  percentile fields carry the quantile sketch's documented
  relative-error tolerance.
* **Executor parity** — ``reference`` and ``blocked`` agree transfer by
  transfer under contention, with a bounded pool that queues.
* **Bounded memory** — slot pools recycle in place (a 1-slot pool still
  completes everything), ingest backpressure bounds the waiting queue,
  and on a forced multi-device host peak RSS does not scale with stream
  length (subprocess test, mirroring tests/test_fleet_sharded.py).
"""
import math
import os
import subprocess
import sys

import numpy as np
import pytest

from repro import api, fleet
from repro.core.types import CHAMELEON, DatasetSpec

FAST = (DatasetSpec("a", 200, 400.0, 2.0),
        DatasetSpec("b", 10, 600.0, 60.0))
ONE = (DatasetSpec("c", 50, 500.0, 10.0),)
BIG = (DatasetSpec("a", 2000, 4000.0, 2.0),
       DatasetSpec("b", 100, 6000.0, 60.0))
NO_CONTENTION = 1e9

HOSTS = dict(nic_mbps=CHAMELEON.bandwidth_mbps, slots=4)


def _trace(n=24, seed=11):
    return fleet.poisson_trace(rate_per_s=0.5, n_transfers=n,
                               datasets=[ONE, FAST],
                               controllers=("eemt", "me", "wget/curl"),
                               profile=CHAMELEON, seed=seed, total_s=600.0)


# ---------------------------------------------------------------- parity --

def _solo(req, dt):
    """An independent api.run of one request's scenario."""
    return api.run(api.Scenario(profile=req.profile, datasets=req.datasets,
                                controller=req.controller,
                                total_s=req.total_s, dt=dt))


def test_online_matches_offline_bit_exactly_on_shared_trace():
    """Zero contention, occupancy that grows and shrinks: every transfer
    equals its own api.run, and the fold's exact totals equal fsum over
    the records."""
    trace = _trace()
    hosts = fleet.host_pool(2, nic_mbps=NO_CONTENTION, slots=4)
    seen = []
    on = fleet.run_fleet_online(trace, hosts, wave_s=10.0, dt=0.5,
                                pool_capacity=64, track_transfers=True,
                                on_wave=seen.append)

    assert on.fold.transfers == len(on.transfers) == len(trace)
    assert len({s["lanes"] for s in seen}) > 1     # the width moved
    by_name = {r.name: r for r in trace}
    solos = {}
    for t in on.transfers:
        req = by_name[t.name]
        key = (req.controller, req.datasets)
        if key not in solos:
            solos[key] = _solo(req, 0.5)
        solo = solos[key]
        assert t.completed and solo.completed
        assert (t.time_s, t.energy_j) == (solo.time_s, solo.energy_j), \
            t.name                          # bit-exact, no tolerance
    # Exact streaming totals == fsum over the records, no tolerance.
    assert on.total_energy_j == math.fsum(t.energy_j for t in on.transfers)
    assert on.total_gb == math.fsum(t.moved_mb for t in on.transfers) / 1024
    assert on.completed == len(trace)
    assert on.dropped == 0
    vals = np.asarray([t.slowdown for t in on.transfers])
    sketch = on.slowdowns()
    for q, key in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
        ref = float(np.percentile(vals, 100 * q, method="inverted_cdf"))
        assert abs(sketch[key] - ref) <= 0.0101 * ref + 1e-12, (key, ref)

    # Per-controller exact fields of the fold bit-match FleetReport's fsum
    # breakdown over the same records, also on the contended trace.
    contended = fleet.run_fleet_online(trace, fleet.host_pool(2, **HOSTS),
                                       wave_s=10.0, dt=0.5, pool_capacity=64,
                                       track_transfers=True)
    assert any(t.wait_s > 0 for t in contended.transfers)
    for rep in (on, contended):
        ref = fleet.FleetReport(transfers=rep.transfers,
                                host_stats=rep.host_stats, sim_s=rep.sim_s,
                                waves=rep.waves, wave_s=10.0, dt=0.5,
                                dropped=0)
        ob, nb = ref.by_controller(), rep.by_controller()
        assert set(ob) == set(nb) and len(ob) > 1
        for name in ob:
            for key in ("transfers", "completed", "energy_j", "gb",
                        "joules_per_gb", "mean_time_s", "mean_wait_s"):
                assert nb[name][key] == ob[name][key], (name, key)
        assert rep.total_energy_j == ref.total_energy_j
        assert rep.total_gb == ref.total_gb


def test_wave_width_follows_highest_occupied_slot():
    """One group: a long lane holds slot 0, four short ones fill slots 1-4
    for one wave and retire.  The wave runs 2, then 8, then 2 lanes again,
    and no transfer's bits move with it."""
    long = fleet.TransferRequest(arrival_s=0.0, datasets=BIG,
                                 controller="me", profile=CHAMELEON,
                                 name="long", total_s=600.0)
    short = [fleet.TransferRequest(arrival_s=1.0, datasets=ONE,
                                   controller="me", profile=CHAMELEON,
                                   name=f"s{i}", total_s=600.0)
             for i in range(4)]
    seen = []
    rep = fleet.run_fleet_online([long] + short,
                                 fleet.host_pool(1, nic_mbps=NO_CONTENTION),
                                 wave_s=5.0, dt=0.5, track_transfers=True,
                                 on_wave=seen.append)
    lanes = [s["lanes"] for s in seen]
    assert lanes[:3] == [2, 8, 2] and set(lanes[2:]) == {2}, lanes
    for req in [long] + short:
        (t,) = [t for t in rep.transfers if t.name == req.name]
        solo = _solo(req, 0.5)
        assert (t.completed, t.time_s, t.energy_j) == \
            (solo.completed, solo.time_s, solo.energy_j), req.name


def test_online_percentiles_within_sketch_tolerance():
    """Sketch p50/p95/p99 vs the nearest-rank reference of the same
    slowdowns (the sketch answers nearest-rank bucket midpoints, so the
    reference must be ``inverted_cdf``, not the interpolating default)."""
    trace = _trace(n=48, seed=12)
    hosts = fleet.host_pool(2, **HOSTS)
    off = fleet.run_fleet(trace, hosts, wave_s=10.0, dt=0.5)
    on = fleet.run_fleet_online(trace, hosts, wave_s=10.0, dt=0.5,
                                pool_capacity=64)
    vals = np.asarray([t.slowdown for t in off.transfers if t.completed])
    sketch = on.slowdowns()
    for q, key in ((0.50, "p50"), (0.95, "p95"), (0.99, "p99")):
        ref = float(np.percentile(vals, 100 * q, method="inverted_cdf"))
        assert abs(sketch[key] - ref) <= 0.0101 * ref + 1e-12, (key, ref)


def test_bounded_pool_preserves_exact_totals():
    """Recycling through a tiny pool delays admissions but must not change
    what each transfer consumes once admitted: totals still exact."""
    reqs = [fleet.TransferRequest(arrival_s=0.0, datasets=ONE,
                                  controller="wget/curl", profile=CHAMELEON,
                                  name=f"r{i}", total_s=600.0)
            for i in range(8)]
    hosts = fleet.host_pool(1, nic_mbps=NO_CONTENTION)
    big = fleet.run_fleet_online(reqs, hosts, wave_s=5.0, dt=0.1,
                                 pool_capacity=64)
    small = fleet.run_fleet_online(reqs, hosts, wave_s=5.0, dt=0.1,
                                   pool_capacity=1)
    assert small.completed == big.completed == 8
    assert small.counters["recycled_slots"] >= 7
    assert small.counters["peak_queue_depth"] >= 7
    # Energy is per-transfer work, unchanged by when a slot frees up.
    assert small.total_energy_j == big.total_energy_j
    assert small.total_gb == big.total_gb
    assert small.sim_s > big.sim_s        # serialization costs time


# ------------------------------------------------------------ edge cases --

def test_empty_stream():
    rep = fleet.run_fleet_online(iter(()), fleet.host_pool(2, **HOSTS))
    assert rep.fold.transfers == 0
    assert rep.waves == 0 and rep.sim_s == 0.0 and rep.dropped == 0
    assert rep.slowdowns() == {"p50": None, "p95": None, "p99": None}
    import json
    json.loads(rep.to_json())             # serializable with no transfers


def test_stream_shorter_than_one_wave():
    """A single sub-wave transfer: one wave runs, and its record equals an
    independent api.run."""
    req = fleet.TransferRequest(arrival_s=0.0, datasets=ONE,
                                controller="wget/curl", profile=CHAMELEON,
                                name="tiny", total_s=600.0)
    hosts = fleet.host_pool(1, nic_mbps=NO_CONTENTION)
    on = fleet.run_fleet_online([req], hosts, wave_s=30.0, dt=0.1,
                                track_transfers=True)
    (t,) = on.transfers
    solo = _solo(req, 0.1)
    assert (t.completed, t.time_s, t.energy_j) == \
        (solo.completed, solo.time_s, solo.energy_j)
    assert on.total_energy_j == t.energy_j
    assert on.waves == 1


def test_all_drained_final_wave_counters_balance():
    rep = fleet.run_fleet_online(_trace(n=12), fleet.host_pool(2, **HOSTS),
                                 wave_s=10.0, dt=0.5)
    c = rep.counters
    assert c["admitted"] == c["retired"] == rep.fold.transfers == 12
    assert rep.dropped == 0
    assert c["waves_run"] == rep.waves >= 1
    assert c["peak_in_flight"] >= 1


def test_idle_gap_fast_forwards_to_next_arrival():
    """A long quiet stretch between arrivals is skipped, not simulated."""
    reqs = [fleet.TransferRequest(arrival_s=t, datasets=ONE,
                                  controller="wget/curl", profile=CHAMELEON,
                                  name=f"g{i}", total_s=600.0)
            for i, t in enumerate((0.0, 10_000.0))]
    rep = fleet.run_fleet_online(reqs, fleet.host_pool(1,
                                                       nic_mbps=NO_CONTENTION),
                                 wave_s=5.0, dt=0.1)
    assert rep.completed == 2
    # Simulated clock covers the gap; actual executed waves do not.
    assert rep.sim_s > 10_000.0
    assert rep.waves < 20


def test_horizon_cut_reports_dropped():
    trace = fleet.poisson_trace(rate_per_s=1.0, n_transfers=20,
                                datasets=[ONE], controllers=["wget/curl"],
                                profile=CHAMELEON, seed=3, total_s=600.0)
    rep = fleet.run_fleet_online(trace,
                                 fleet.host_pool(1, nic_mbps=NO_CONTENTION,
                                                 slots=1),
                                 wave_s=5.0, dt=0.1, horizon_s=10.0)
    assert rep.dropped > 0
    # Unlike run_fleet's, the stream is consumed lazily: arrivals past the
    # horizon are never ingested, so dropped counts only the queued ones.
    assert rep.fold.transfers + rep.dropped <= len(trace)
    assert rep.sim_s == 10.0


# ---------------------------------------------------------- backpressure --

def test_backpressure_pauses_ingest_and_still_completes():
    reqs = [fleet.TransferRequest(arrival_s=0.0, datasets=ONE,
                                  controller="wget/curl", profile=CHAMELEON,
                                  name=f"b{i}", total_s=3600.0)
            for i in range(40)]
    rep = fleet.run_fleet_online(reqs,
                                 fleet.host_pool(1, nic_mbps=NO_CONTENTION,
                                                 slots=2),
                                 wave_s=5.0, dt=0.1, pool_capacity=2,
                                 queue_high=4, queue_low=1)
    assert rep.completed == 40
    assert rep.counters["ingest_paused_waves"] > 0
    assert rep.counters["peak_queue_depth"] <= 4


def test_on_wave_observability_callback():
    seen = []
    fleet.run_fleet_online(_trace(n=6), fleet.host_pool(2, **HOSTS),
                           wave_s=10.0, dt=0.5, on_wave=seen.append)
    assert len(seen) >= 1
    for snap in seen:
        assert {"wave", "now", "queue_depth", "in_flight", "admitted",
                "retired", "ingest_paused", "recycled"} <= set(snap)
    assert sum(s["retired"] for s in seen) == 6


# ------------------------------------------------------------ validation --

def test_reference_executor_matches_blocked_under_contention():
    """The reference wave path runs in the loop and equals blocked,
    transfer by transfer: shared NICs (shares < 1), host slot budgets and
    a 2-slot pool that makes requests queue."""
    reqs = [fleet.TransferRequest(arrival_s=0.3 * i, datasets=FAST,
                                  controller=c, profile=CHAMELEON,
                                  name=f"t{i}-{c}", total_s=240.0)
            for i in range(4) for c in ("eemt", "me")]
    hosts = fleet.host_pool(2, nic_mbps=800.0, slots=3)
    reps = {ex: fleet.run_fleet_online(reqs, hosts, wave_s=5.0, dt=0.1,
                                       pool_capacity=2, executor=ex,
                                       track_transfers=True)
            for ex in ("reference", "blocked")}
    a, b = reps["reference"], reps["blocked"]
    assert a.counters["peak_queue_depth"] > 0
    assert any(t.wait_s > 0 for t in b.transfers)
    assert b.completed == len(reqs)
    assert a.transfers == b.transfers        # frozen records: bit-exact
    assert a.host_stats == b.host_stats
    assert (a.sim_s, a.waves) == (b.sim_s, b.waves)


def test_too_many_partitions_names_the_knob():
    wide = tuple(DatasetSpec(f"d{i}", 5, 100.0, 1.0) for i in range(4))
    req = fleet.TransferRequest(arrival_s=0.0, datasets=wide,
                                controller="wget/curl", profile=CHAMELEON,
                                total_s=600.0)
    with pytest.raises(ValueError, match="max_partitions"):
        fleet.run_fleet_online([req], fleet.host_pool(1, **HOSTS),
                               max_partitions=2)


def test_config_validation():
    with pytest.raises(ValueError):
        fleet.OnlineConfig(pool_capacity=0)
    with pytest.raises(ValueError):
        fleet.OnlineConfig(queue_low=10, queue_high=5)


def test_api_reexports_online_entry_points():
    assert api.run_fleet_online is fleet.run_fleet_online
    assert api.OnlineConfig is fleet.OnlineConfig
    assert api.poisson_stream is fleet.poisson_stream
    assert api.diurnal_stream is fleet.diurnal_stream
    assert api.replay_stream is fleet.replay_stream


# -------------------------------------------------------------- streams --

def test_poisson_stream_is_lazy_deterministic_and_sorted():
    kw = dict(rate_per_s=2.0, datasets=[ONE, FAST],
              controllers=("eemt", "me"), profile=CHAMELEON, seed=42,
              n_transfers=50)
    a = list(fleet.poisson_stream(**kw))
    b = list(fleet.poisson_stream(**kw))
    assert a == b and len(a) == 50
    arrivals = [r.arrival_s for r in a]
    assert arrivals == sorted(arrivals)
    # Unbounded form: take a prefix without materializing anything.
    it = fleet.poisson_stream(**{**kw, "n_transfers": None})
    prefix = [next(it) for _ in range(10)]
    assert len(prefix) == 10


def test_diurnal_stream_rate_modulation_and_validation():
    reqs = list(fleet.diurnal_stream(base_rate_per_s=0.5,
                                     peak_rate_per_s=20.0, period_s=100.0,
                                     datasets=[ONE],
                                     controllers=("wget/curl",),
                                     profile=CHAMELEON, seed=1,
                                     n_transfers=400))
    arrivals = np.asarray([r.arrival_s for r in reqs])
    assert (np.diff(arrivals) >= 0.0).all()
    # More arrivals near mid-period (peak) than near period start (base).
    phase = np.mod(arrivals, 100.0)
    near_peak = ((phase > 25.0) & (phase < 75.0)).sum()
    assert near_peak > len(reqs) // 2
    with pytest.raises(ValueError):
        next(fleet.diurnal_stream(base_rate_per_s=5.0, peak_rate_per_s=1.0,
                                  period_s=100.0, datasets=[ONE],
                                  controllers=("wget/curl",),
                                  profile=CHAMELEON))


def test_replay_stream_rejects_unsorted():
    r0 = fleet.TransferRequest(arrival_s=5.0, datasets=ONE,
                               controller="wget/curl", profile=CHAMELEON,
                               name="late", total_s=600.0)
    r1 = fleet.TransferRequest(arrival_s=1.0, datasets=ONE,
                               controller="wget/curl", profile=CHAMELEON,
                               name="early", total_s=600.0)
    with pytest.raises(ValueError, match="arrival"):
        list(fleet.replay_stream([r0, r1]))


# ----------------------------------------------- multi-device (forced) --

_SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import resource
import jax
assert jax.device_count() == 8, jax.devices()

from repro import fleet
from repro.core.types import CHAMELEON, DatasetSpec
from repro.distributed.sharding import MeshConfig

ONE = (DatasetSpec("c", 50, 500.0, 10.0),)
HOSTS = fleet.host_pool(4, nic_mbps=CHAMELEON.bandwidth_mbps, slots=8)
MESH = MeshConfig(num_hosts=2, devices_per_host=4)
assert len(MESH.devices()) == 8

def stream(n):
    return fleet.poisson_stream(rate_per_s=2.0, datasets=[ONE],
                                controllers=("eemt", "wget/curl"),
                                profile=CHAMELEON, seed=9, n_transfers=n,
                                total_s=1e9)

KW = dict(wave_s=10.0, dt=0.5, pool_capacity=16)

# Sharded mesh execution reproduces the single-device online results.
flat = fleet.run_fleet_online(stream(24), HOSTS, track_transfers=True, **KW)
mesh = fleet.run_fleet_online(stream(24), HOSTS, track_transfers=True,
                              mesh=MESH, **KW)
assert mesh.fold.transfers == flat.fold.transfers == 24
assert mesh.completed == flat.completed
assert mesh.total_energy_j == flat.total_energy_j, \
    (mesh.total_energy_j, flat.total_energy_j)
for m, f in zip(mesh.transfers, flat.transfers):
    assert m == f, (m, f)
print("ONLINE-MESH-PARITY-OK")

# Bounded memory: a 10x longer stream through the same pools must not
# move peak RSS (pools and sketches are fixed-size; only the stream
# position advances).
def rss_mb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0

fleet.run_fleet_online(stream(60), HOSTS, mesh=MESH, **KW)
rss_small = rss_mb()
fleet.run_fleet_online(stream(600), HOSTS, mesh=MESH, **KW)
rss_big = rss_mb()
growth = rss_big - rss_small
assert growth < 128.0, (rss_small, rss_big)
print(f"ONLINE-RSS-FLAT-OK growth={growth:.1f}MB")
"""


def test_online_fleet_on_forced_multi_device_host():
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), os.pardir, "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SCRIPT],
                          capture_output=True, text=True, env=env,
                          timeout=600)
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "ONLINE-MESH-PARITY-OK" in proc.stdout
    assert "ONLINE-RSS-FLAT-OK" in proc.stdout
