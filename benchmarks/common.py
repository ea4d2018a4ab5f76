"""Shared tables for the benchmark harness.

Every benchmark prints CSV rows:  name,us_per_call,derived
where ``us_per_call`` is the wall-clock microseconds of the measured call
and ``derived`` is the benchmark's headline metric (throughput, joules, ...).

The figure suites (fig2/fig3/fig4) run their whole grid through one
``repro.api.Experiment`` and report the *steady-state* sweep total divided
by the cell count in the ``us_per_call`` column: per-cell wall time has no
meaning when many cells share one vmapped XLA launch, so treat those values
as grid-amortized.  Compile time is measured separately (the cold/warm
split in ``Experiment.run(timing="split")``) and lands in the BENCH JSON
records as ``*_compile_s``, never folded into ``us_per_call``.

Grid enumeration, sweep execution, and result tabulation all live in
``repro.api.experiments`` now — this module only keeps the profile/dataset
tables the paper's figures share, and the one-line CSV emitter.
"""
from __future__ import annotations

import os
from pathlib import Path

from repro.core.types import (CHAMELEON, CLOUDLAB, DIDCLAB, LARGE_FILES,
                              MEDIUM_FILES, MIXED, SMALL_FILES)

DATASETS = {
    "small": (SMALL_FILES,),
    "medium": (MEDIUM_FILES,),
    "large": (LARGE_FILES,),
    "mixed": MIXED,
}

TESTBEDS = {
    "chameleon": CHAMELEON,
    "cloudlab": CLOUDLAB,
    "didclab": DIDCLAB,
}


def budget_for(prof) -> float:
    """Per-testbed transfer time budget (seconds): low-bandwidth testbeds
    (CloudLab/DIDCLab, 1 Gbps) get the longer window the paper allows."""
    return 28800.0 if prof.bandwidth_mbps < 500 else 7200.0


def emit(name: str, seconds: float, derived) -> str:
    row = f"{name},{seconds * 1e6:.0f},{derived}"
    print(row, flush=True)
    return row


#: Where compiled programs persist between runs when nothing else says so:
#: a fixed path (it is part of every entry's key, so it must not move).
COMPILE_CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here; otherwise the cache is the repository's
    ``.jax_cache/``.  Call before the first compile.  Returns the
    directory in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
