"""Ring-buffer slot pool + streaming aggregate invariants.

Property tests (hypothesis, importorskip-guarded like the other suites)
for the structures the online fleet's bounded-memory claim rests on:

* :class:`repro.fleet.ringbuf.SlotPool` — no slot aliasing (a slot is
  never live twice), capacity never exceeded, every allocation takes the
  lowest free slot, release really recycles, and the wave width covers
  the highest occupied slot in powers of two.
* :class:`repro.fleet.aggregates.ExactSum` — exactly rounded and
  order-independent (the bit-equality mechanism for online totals).
* :class:`repro.fleet.aggregates.QuantileSketch` — quantiles within the
  documented relative-error bound of the nearest-rank reference, under
  any insertion order.
"""
import math
import random

import numpy as np
import pytest

from repro.core import tickstate
from repro.fleet.aggregates import ExactSum, QuantileSketch
from repro.fleet.ringbuf import SlotPool, wave_width

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:           # property tests skip; deterministic ones run
    HAVE_HYPOTHESIS = False

    def given(*a, **kw):      # no-op decorators so the module still imports
        return lambda fn: pytest.mark.skip(
            reason="hypothesis not installed")(fn)

    settings = given

    class _StrategyStub:
        def __getattr__(self, name):
            return lambda *a, **kw: None

    st = _StrategyStub()

LAY = tickstate.TickLayout(2)


# ------------------------------------------------------------- SlotPool --

@settings(max_examples=60, deadline=None)
@given(capacity=st.integers(1, 9),
       ops=st.lists(st.integers(0, 2 ** 30), min_size=1, max_size=120))
def test_slot_pool_invariants(capacity, ops):
    """Random alloc/release interleavings: no aliasing, no over-capacity,
    lowest free slot first, width covering the highest occupied slot."""
    pool = SlotPool(capacity, LAY)
    live = set()
    ever = set()
    for op in ops:
        if op % 2 == 0 or not live:           # alloc
            slot = pool.alloc()
            if len(live) == capacity:
                assert slot is None            # capacity never exceeded
            else:
                assert slot is not None and slot not in live  # no aliasing
                assert slot == min(set(range(capacity)) - live)
                pool.f32[slot, 0] = 1.0        # mark: release must zero it
                live.add(slot)
                ever.add(slot)
        else:                                  # release a random live slot
            slot = sorted(live)[op % len(live)]
            pool.release(slot)
            live.remove(slot)
            assert pool.f32[slot].sum() == 0.0  # zeroed on retire
        assert pool.in_flight == len(live)
        assert set(pool.active_slots().tolist()) == live
        w = pool.width()
        top = max(live) + 1 if live else 0
        assert w >= max(top, 2) and w & (w - 1) == 0   # a power of two
        assert w < 2 * max(top, 2)                     # ... the smallest
        assert w <= len(pool.f32)                      # ... and it fits
    assert pool.peak_in_flight <= capacity
    # total recycles = allocations beyond the first use of each slot
    assert pool.recycled == pool.total_allocs - len(ever)


def test_slot_pool_release_inactive_raises():
    pool = SlotPool(2, LAY)
    with pytest.raises(ValueError):
        pool.release(0)


def test_slot_pool_lowest_free_slot_and_packed_width():
    """Freed slots are reused lowest-first, so occupied slots stay packed
    and the wave width falls back as soon as the high slots free."""
    pool = SlotPool(6, LAY)
    assert len(pool.f32) == 8                  # room for the widest wave
    assert pool.width() == 2                   # an empty pool still runs 2
    a, b, c, d, e = (pool.alloc() for _ in range(5))
    assert (a, b, c, d, e) == (0, 1, 2, 3, 4)
    assert pool.width() == 8
    pool.release(b)
    pool.release(a)
    assert pool.width() == 8                   # slot 4 holds the width up
    assert pool.alloc() == a                   # lowest free, not oldest
    assert pool.alloc() == b
    for s in (c, d, e):
        pool.release(s)
    assert pool.width() == 2                   # slots 0 and 1 remain
    assert pool.recycled == 2
    assert [wave_width(n) for n in (0, 1, 2, 3, 5, 8, 9)] == \
        [2, 2, 2, 4, 8, 8, 16]
    assert wave_width(5, 4) == 8 and wave_width(3, 3) == 6  # whole shards
    assert len(SlotPool(6, LAY, multiple=3).f32) == 9


# ------------------------------------------------------------- ExactSum --

# Width-32 draws (the fold adds float32 state components) need bounds that
# float32 represents exactly: 2**40 (~1.1e12) is, 1e12 is not.
@settings(max_examples=60, deadline=None)
@given(st.lists(st.floats(min_value=-2.0**40, max_value=2.0**40,
                          allow_nan=False, allow_infinity=False,
                          width=32),
                min_size=0, max_size=200),
       st.randoms(use_true_random=False))
def test_exact_sum_is_order_independent_and_exact(values, rng):
    """ExactSum == math.fsum regardless of accumulation order."""
    want = math.fsum(values)
    acc = ExactSum()
    for v in values:
        acc.add(v)
    assert acc.value() == want
    shuffled = list(values)
    rng.shuffle(shuffled)
    acc2 = ExactSum()
    for v in shuffled:
        acc2.add(v)
    assert acc2.value() == want


# -------------------------------------------------------- QuantileSketch --

@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(min_value=1e-3, max_value=1e7,
                          allow_nan=False, allow_infinity=False),
                min_size=1, max_size=300),
       st.sampled_from([0.5, 0.95, 0.99]))
def test_quantile_sketch_relative_error_bound(values, q):
    """Sketch quantile within rel_err of the nearest-rank reference."""
    sk = QuantileSketch(rel_err=0.01)
    for v in values:
        sk.add(v)
    got = sk.quantile(q)
    ref = float(np.percentile(np.asarray(values), 100 * q,
                              method="inverted_cdf"))
    assert abs(got - ref) <= 0.0101 * ref + 1e-12


def test_quantile_sketch_order_invariant_and_empty():
    sk = QuantileSketch()
    assert sk.quantile(0.5) is None
    assert sk.percentiles() == {"p50": None, "p95": None, "p99": None}
    vals = [random.Random(0).uniform(0.1, 1e4) for _ in range(500)]
    a, b = QuantileSketch(), QuantileSketch()
    for v in vals:
        a.add(v)
    for v in reversed(vals):
        b.add(v)
    assert a.percentiles() == b.percentiles()
    assert np.array_equal(a.counts, b.counts)


def test_quantile_sketch_memory_is_fixed():
    """Bucket array size never grows with the stream (bounded memory)."""
    sk = QuantileSketch()
    n0 = len(sk.counts)
    for i in range(10_000):
        sk.add(0.01 * (i + 1))
    assert len(sk.counts) == n0
    assert sk.n == 10_000
