"""repro.fleet — trace-driven, fleet-scale transfer simulation.

The paper evaluates tuners one transfer at a time; its motivation (100+ TWh
of global data-movement energy) is a *fleet* problem.  This package runs
thousands of concurrent transfers — Poisson or replayed-trace arrivals
across a pool of hosts, each host with a transfer-slot budget and a shared
NIC whose capacity is split among its in-flight transfers — on top of the
``repro.api`` Scenario/engine substrate.

Execution is in streaming *waves*: all active transfers advance by one wave
window through the grouped ``jit(vmap(scan))`` engine (one launch per
(controller code, environment code, cpu) group, as wide as the group's
occupancy in powers of two), completed lanes are drained and refilled from
the arrival queue, and per-host NIC contention rescales each transfer's
available bandwidth between waves.  Pools may be heterogeneous: every
:class:`Host` carries its own CPU profile and its own
``repro.api`` Environment (reference / lossy-WAN / big.LITTLE / custom),
and each distinct physics compiles its own wave runner.

Quickstart::

    from repro import fleet
    from repro.core.types import CHAMELEON, DatasetSpec

    hosts = fleet.host_pool(8, nic_mbps=1250.0, slots=16)
    trace = fleet.poisson_trace(
        rate_per_s=2.0, n_transfers=1000, seed=0,
        datasets=((DatasetSpec("d", 100, 2000.0, 20.0),),),
        controllers=("eemt", "me", "wget/curl"),
        profile=CHAMELEON)
    report = fleet.run_fleet(trace, hosts, wave_s=30.0, dt=0.1)
    print(report.summary())

There is one fleet loop, :func:`run_fleet_online` (``repro.fleet.online``):
it takes *unbounded* arrival streams with fixed host memory regardless of
stream length (see the stream adapters ``poisson_stream``,
``diurnal_stream``, ``replay_stream``), and ``run_fleet`` runs it on a
finite trace with nothing bounded, keeping every per-transfer record.
"""
from .aggregates import (FleetFold, FleetReport,  # noqa: F401
                         FleetTransfer, OnlineFleetReport, QuantileSketch)
from .arrivals import (TransferRequest, diurnal_stream,  # noqa: F401
                       poisson_stream, poisson_trace, replay_stream,
                       replay_trace)
from .hosts import Host, host_pool  # noqa: F401
from .online import OnlineConfig, run_fleet, run_fleet_online  # noqa: F401
from .ringbuf import SlotPool  # noqa: F401

__all__ = [
    "FleetFold", "FleetReport", "FleetTransfer", "Host", "OnlineConfig",
    "OnlineFleetReport", "QuantileSketch", "SlotPool", "TransferRequest",
    "diurnal_stream", "host_pool", "poisson_stream", "poisson_trace",
    "replay_stream", "replay_trace", "run_fleet", "run_fleet_online",
]
