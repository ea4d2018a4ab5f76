"""The comparison that decides ``correct``: the program's answers against
the reference's, one number per kind of gap, each against its limit.

The limits live in the configuration file (``limits``), set from the
largest gap sound runs of the program showed and the smallest gap the
control (the reference in bfloat16 in the program's place) showed; see
PERF.md for the readings.
"""
from __future__ import annotations

from typing import NamedTuple


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


def rel_gap(got: float, want: float) -> float:
    return abs(got - want) / max(abs(want), 1e-9)


def gap_checks(got: list, want: list, dt: float, limits: dict) -> list:
    """Answers paired in order: ``completed`` must agree; ``time_s`` is
    compared in ticks, ``energy_j`` and ``moved_mb`` relatively.  Each
    number is the worst over all pairs."""
    if len(got) != len(want):
        raise ValueError(f"{len(got)} answers against {len(want)}")
    pairs = list(zip(got, want))
    values = {
        "completed_diff": sum(g["completed"] != w["completed"]
                              for g, w in pairs),
        "time_gap_ticks": max((abs(g["time_s"] - w["time_s"]) / dt
                               for g, w in pairs), default=0.0),
        "energy_gap": max((rel_gap(g["energy_j"], w["energy_j"])
                           for g, w in pairs), default=0.0),
        "moved_gap": max((rel_gap(g["moved_mb"], w["moved_mb"])
                          for g, w in pairs), default=0.0),
    }
    return [Check(k, float(v), float(limits[k])) for k, v in values.items()]


def format_checks(checks: list) -> dict:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}
