"""Fixed-capacity slot pools for fleet lanes.

The fleet loop (``repro.fleet.online``) keeps every in-flight transfer in
a slot of its wave-runner group's :class:`SlotPool`:

* **Bounded memory.**  All lane state lives in preallocated arrays — the
  two flat ``TickLayout`` state rows, the shared parameter row, and the
  scalar per-lane bookkeeping (step counters, tick budgets, host indices,
  timestamps).  Host memory is a function of ``capacity``, never of how
  many transfers the stream has carried.
* **Packed slots, waves as wide as the occupancy.**  :meth:`alloc` hands
  out the lowest free slot, so occupied slots stay packed at the low
  indices, and a wave runs only the pool's first :meth:`width` rows: the
  smallest power of two that is at least 2 and covers the highest
  occupied slot (:func:`wave_width`).  A group therefore compiles
  O(log capacity) wave programs.  Free slots inside the width hold zeroed
  rows — a zeroed lane has no bytes remaining, so the engine's completion
  masking freezes it from tick 0.  A long-lived lane in a high slot holds
  the width up until it retires: that is the price of keeping slots in
  place instead of restacking lanes every wave.
* **Recycling in place.**  A retired slot's rows are zeroed and the next
  admission overwrites them; nothing is ever appended or reallocated.

Invariants (property-tested in tests/test_ringbuf.py): a slot is never
handed out twice without an intervening :meth:`release`, occupancy never
exceeds ``capacity`` (:meth:`alloc` returns ``None`` when full), and every
allocation takes the lowest free slot.
"""
from __future__ import annotations

import heapq

import numpy as np

from repro.core import tickstate


def wave_width(lanes: int, multiple: int = 1) -> int:
    """Rows a wave runs to cover slots ``[0, lanes)``: the smallest power
    of two that is at least ``max(lanes, 2)``, rounded up to a multiple of
    ``multiple`` (the device count of a sharded wave).

    The floor of 2 keeps the fleet off one-lane programs, which the sweep
    avoids too: on the CPU a one-lane program rounds the energy sum
    differently from a wider one."""
    w = 1 << (max(lanes, 2) - 1).bit_length()
    return -(-w // multiple) * multiple


class SlotPool:
    """Preallocated lane storage for one wave-runner group.

    One pool exists per (controller code, environment code, cpu, stride)
    group, so every slot of a pool is shape- and code-compatible with its
    wave runner.  ``capacity`` bounds the in-flight lanes; the arrays hold
    ``wave_width(capacity, multiple)`` rows, so every wave's width fits.
    """

    __slots__ = ("capacity", "layout", "params", "bw", "f32", "i32",
                 "steps_done", "done_at", "budget", "host_idx", "start_s",
                 "arrival_s", "ideal_s", "demand_mbps", "names",
                 "ctrl_names", "reqs", "combos", "_active", "_free",
                 "_used", "in_flight", "peak_in_flight", "recycled",
                 "total_allocs")

    def __init__(self, capacity: int, layout: tickstate.TickLayout,
                 multiple: int = 1):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self.layout = layout
        c = wave_width(self.capacity, multiple)
        self.params = np.zeros((c, layout.params_size), np.float32)
        self.bw = np.ones((c,), np.float32)
        self.f32 = np.zeros((c, layout.f32_size), np.float32)
        self.i32 = np.zeros((c, layout.i32_size), np.int32)
        self.steps_done = np.zeros((c,), np.int32)
        self.done_at = np.full((c,), -1, np.int32)
        self.budget = np.zeros((c,), np.int32)
        self.host_idx = np.full((c,), -1, np.int32)
        self.start_s = np.zeros((c,), np.float64)
        self.arrival_s = np.zeros((c,), np.float64)
        self.ideal_s = np.zeros((c,), np.float64)
        self.demand_mbps = np.zeros((c,), np.float64)
        self.names: list = [None] * c
        self.ctrl_names: list = [None] * c
        # References, not copies: the admitted TransferRequest and its
        # shared Combo — what fault injection reads to build the requeue
        # (remaining-bytes resume) and the churn ledger's offered
        # components, and what the reference executor stacks its inputs
        # from.  Still O(capacity) memory.
        self.reqs: list = [None] * c
        self.combos: list = [None] * c
        self._active = np.zeros((c,), bool)
        self._free: list = []        # a min-heap of released slots
        self._used = 0               # slots [0, _used) have been handed out
        self.in_flight = 0
        self.peak_in_flight = 0
        self.recycled = 0            # allocations that reused a freed slot
        self.total_allocs = 0

    # ------------------------------------------------------- alloc/free --

    def alloc(self) -> "int | None":
        """Claim the lowest free slot, or None when full.

        The slot's state rows are the zeros :meth:`release` left (or the
        pool was born with); the caller overwrites them with the admitted
        lane's combo rows and bookkeeping.
        """
        # Lowest-first allocation keeps the used slots a prefix, so every
        # released slot lies below ``_used``: the heap holds only those.
        if self._free:
            slot = heapq.heappop(self._free)
            self.recycled += 1
        elif self._used < self.capacity:
            slot = self._used
            self._used += 1
        else:
            return None
        self._active[slot] = True
        self.in_flight += 1
        self.peak_in_flight = max(self.peak_in_flight, self.in_flight)
        self.total_allocs += 1
        return slot

    def release(self, slot: int) -> None:
        """Retire a slot: zero its rows (a zeroed lane is born drained, so
        a wave that covers it freezes it from tick 0) and free it."""
        if not self._active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self._active[slot] = False
        self.params[slot] = 0.0
        self.bw[slot] = 1.0
        self.f32[slot] = 0.0
        self.i32[slot] = 0
        self.steps_done[slot] = 0
        self.done_at[slot] = -1
        self.budget[slot] = 0
        self.host_idx[slot] = -1
        self.start_s[slot] = 0.0
        self.arrival_s[slot] = 0.0
        self.ideal_s[slot] = 0.0
        self.demand_mbps[slot] = 0.0
        self.names[slot] = None
        self.ctrl_names[slot] = None
        self.reqs[slot] = None
        self.combos[slot] = None
        heapq.heappush(self._free, slot)
        self.in_flight -= 1

    # ------------------------------------------------------------ views --

    def active_slots(self) -> np.ndarray:
        """Indices of occupied slots, ascending (deterministic iteration
        order for retirement and aggregation)."""
        return np.flatnonzero(self._active)

    def is_active(self, slot: int) -> bool:
        return bool(self._active[slot])

    def width(self, multiple: int = 1) -> int:
        """Rows the next wave runs: :func:`wave_width` of the packed prefix
        up to the highest occupied slot."""
        act = self.active_slots()
        return wave_width(int(act[-1]) + 1 if act.size else 0, multiple)
