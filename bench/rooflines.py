"""Bytes the tick semantics require, and the HBM roofline they imply.

One lane-tick reads and writes the lane's tick state once and reads its
parameter row once.  The state is the simulator's flat ``TickLayout``
(``repro/core/tickstate.py``): for ``P`` partitions, an f32 row of
``2P + 9`` slots (remaining and window per partition; t, energy, bytes
moved; six controller floats) and an i32 row of 3 (FSM state, cores,
frequency index); the parameter row holds ``13 + 5P`` f32 slots (six path
and seven tuner scalars; pp, par, size, file size and weight per
partition).  The widths are written out here so that the yardstick does
not move with the program.

The lane-ticks are counted from results, not from what ran: a grid cell
needs its completion ticks.  Work a runner does beyond that (ticks past
completion, padded lanes) is what a faster runner may drop.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Iterable

F32_BYTES = I32_BYTES = 4
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def state_slots(n_partitions: int) -> tuple[int, int]:
    """(f32 slots, i32 slots) of one lane's tick state."""
    return 2 * n_partitions + 9, 3


def param_slots(n_partitions: int) -> int:
    return 13 + 5 * n_partitions


def bytes_per_lane_tick(n_partitions: int) -> int:
    """State read and written once, parameters read once."""
    f32, i32 = state_slots(n_partitions)
    state = f32 * F32_BYTES + i32 * I32_BYTES
    return 2 * state + param_slots(n_partitions) * F32_BYTES


def grid_tick_bytes(times_s: Iterable[float], partitions: Iterable[int],
                    dt: float) -> int:
    """Bytes a grid pass needs: each cell's completion (or horizon) time in
    ticks, times the bytes of a lane-tick at the cell's partition count."""
    return sum(int(round(t / dt)) * bytes_per_lane_tick(p)
               for t, p in zip(times_s, partitions))


def peaks(device_kind: str) -> dict:
    """The peak table row of ``device_kind``; a device that is not in the
    table is an error, never a default."""
    table = json.loads(PEAKS_FILE.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def roofline_pct(n_bytes: float, busy_s: float, peak: dict) -> float:
    """Share of the HBM roofline: the least time the bytes need at the
    peak bandwidth of ``peak`` (a row of the table) over the device's busy
    time, in percent."""
    if busy_s <= 0:
        raise ValueError("busy time must be positive")
    return 100.0 * n_bytes / peak["hbm_bytes_per_s"] / busy_s
