"""The tick roofline's counts, checked by hand: bytes per lane-tick from
the tick state's widths, lane-ticks from results, and the peak table."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import rooflines  # noqa: E402


def test_bytes_per_lane_tick_by_hand():
    # P = 1: f32 row 2 + 9 = 11 slots, i32 row 3, both read and written:
    # 2 x (44 + 12) = 112; parameter row 13 + 5 = 18 slots read: 72.
    assert rooflines.bytes_per_lane_tick(1) == 112 + 72
    # P = 3: 2 x (15 x 4 + 12) = 144; parameters 28 x 4 = 112.
    assert rooflines.bytes_per_lane_tick(3) == 144 + 112


def test_one_grid_cell_by_hand():
    """A Chameleon mixed-dataset EEMT transfer: its completion ticks from
    the program's result, at 256 bytes a lane-tick (three partitions)."""
    from repro import api
    from repro.core import CHAMELEON, MIXED

    r = api.run(api.Scenario(profile=CHAMELEON, datasets=MIXED,
                             controller="EEMT", total_s=600.0))
    assert r.completed
    ticks = round(r.time_s / 0.1)
    assert rooflines.grid_tick_bytes([r.time_s], [3], 0.1) == ticks * 256


def test_roofline_share():
    # 819 MB in 1 s of busy time on a chip of 819 GB/s: 0.1 %.
    assert rooflines.roofline_pct(
        819e6, 1.0, rooflines.peaks("TPU v5 lite")) == \
        pytest.approx(0.1)


def test_unknown_device_is_an_error():
    assert rooflines.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        rooflines.peaks("cpu")
