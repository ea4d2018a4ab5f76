"""Whole grid passes: every cell of the configuration's grid once, in an
order drawn from the seed, each at the full path rate (the paper's
testbeds, no background traffic).  Every seed offers the same work; the
seed moves each transfer to another lane of its group."""
from __future__ import annotations

from bench.generators import rng_for


def plan(seed: int, mix: dict, n_cells: int) -> list[tuple[int, dict]]:
    return [(int(k), {}) for k in rng_for(seed, 3).permutation(n_cells)]
