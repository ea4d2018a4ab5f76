"""Traffic generators.  Each mix under ``traffic/`` names one by its
``generator`` key, and the harness loads ``generators/<generator>.py`` by
that name, so a mix that needs a new kind of traffic adds a file here and
edits none.

A grid generator defines ``plan(seed, mix, n_cells)``: the cells one grid
pass hands to the sweep, in order, as ``(cell index, overrides)`` pairs.
The overrides replace fields of the cell's scenario; the grid workload
accepts those the reference follows (``bw_schedule``: the per-tick share
of the path rate).

Each generator is a pure function of ``--seed`` and the mix's parameters,
so two runs with one seed offer the same work.  Generators are kept with
the benchmark, not called in the program, so that a change to the
program cannot move the yardstick.
"""
from __future__ import annotations

import importlib

import numpy as np


def load(name: str):
    """The generator module a mix names."""
    return importlib.import_module(f"bench.generators.{name}")


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of one run seed (any integer,
    negative or beyond 64 bits included)."""
    return np.random.default_rng([seed % 2 ** 64, *stream])
