"""Host spans and a program counter, on the profiler's clock.

``span(name, **meta)`` marks one stretch of host work.  It always opens a
``jax.profiler.TraceAnnotation``, so the span lands on the profiler's host
plane on the same clock as the device planes; with no trace active a
span costs one or two microseconds of host time.  While a profiler trace
is active (read once as the span opens) it also keeps a :class:`Record`
in memory, at most ``CAP`` of them: past that, records are dropped and
counted (:func:`dropped`).  :func:`spans` reads the records and :func:`clear`
resets them.

The compile counter is always on.  One listener on JAX's own compile
events, registered when this module is imported, counts ``programs``
(backend compiles, persistent-cache loads included: JAX times a cache
load as a backend compile) and ``compile_s`` (trace, lower and compile
seconds), each under the name of the innermost open span of the compiling
thread (``None`` outside any span).  :func:`counters` reads them.

The spans that ``repro.api.sweep`` opens are described in the README's
"Tracing a sweep".
"""
from __future__ import annotations

import itertools
import threading
import time
from collections import defaultdict
from typing import NamedTuple, Optional

import jax

CAP = 65_536                # records kept while a trace is active

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   _BACKEND_COMPILE)


class Record(NamedTuple):
    """One closed span; times in ns on the wall clock, as the profiler's.
    ``trace_id`` is the span id of the outermost open span (a ``sweep``
    call), shared by every span inside it."""

    name: str
    span_id: int
    parent_id: Optional[int]
    trace_id: int
    start_ns: int
    end_ns: int
    meta: dict


class _Stack(threading.local):
    def __init__(self):
        self.open: list = []       # the thread's open spans, innermost last


_stack = _Stack()
_ids = itertools.count(1)
_lock = threading.Lock()
_records: list[Record] = []
_dropped = 0
_programs: dict = defaultdict(int)
_compile_s: dict = defaultdict(float)


class span:
    """Context manager: one host span (see the module docstring).
    ``annotate(**meta)`` adds meta known only once the span is open."""

    __slots__ = ("name", "meta", "span_id", "parent_id", "trace_id",
                 "_ann", "_start")

    def __init__(self, name: str, **meta):
        self.name, self.meta = name, meta

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.meta)
        self._ann.__enter__()
        self.span_id = next(_ids)
        open_ = _stack.open
        parent = open_[-1] if open_ else None
        self.parent_id = parent.span_id if parent else None
        self.trace_id = parent.trace_id if parent else self.span_id
        self._start = (time.time_ns() if self._ann.is_enabled()
                       else None)
        open_.append(self)
        return self

    def annotate(self, **meta) -> None:
        self.meta.update(meta)
        self._ann.set_metadata(**meta)

    def __exit__(self, *exc) -> None:
        global _dropped
        _stack.open.pop()
        if self._start is not None:
            rec = Record(self.name, self.span_id, self.parent_id,
                         self.trace_id, self._start, time.time_ns(),
                         self.meta)
            with _lock:
                if len(_records) < CAP:
                    _records.append(rec)
                else:
                    _dropped += 1
        self._ann.__exit__(*exc)


def spans() -> list[Record]:
    """The records kept so far, in the order the spans closed."""
    with _lock:
        return list(_records)


def dropped() -> int:
    """Records dropped past ``CAP`` since the last :func:`clear`."""
    return _dropped


def clear() -> None:
    global _dropped
    with _lock:
        _records.clear()
        _dropped = 0


def counters() -> dict:
    """``{"programs": {span name: n}, "compile_s": {span name: s}}`` for
    the process so far."""
    with _lock:
        return {"programs": dict(_programs), "compile_s": dict(_compile_s)}


def _on_compile(event: str, duration: float, **_) -> None:
    if event not in _COMPILE_EVENTS:
        return
    open_ = _stack.open
    name = open_[-1].name if open_ else None
    with _lock:
        _compile_s[name] += duration
        if event == _BACKEND_COMPILE:
            _programs[name] += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile)
