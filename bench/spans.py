"""Host milliseconds of the sweep's program spans, per unit of the
traced window.

``repro.obs`` keeps a record of every span the program opens while the
profiler traces; the traced window runs in this process, so after it
:func:`records` holds exactly the window's spans.  The five leaves of a
sweep (``LEAVES``) do not overlap, so what they leave of the window is
host work no span covers.  A program without ``repro.obs`` keeps no
records: then every reading here is ``None``.
"""
from __future__ import annotations

LEAVES = ("sweep.prepare", "sweep.launch", "sweep.wait", "sweep.fetch",
          "sweep.postprocess")


def records() -> list | None:
    """The program's span records, or ``None`` where it keeps none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return obs.spans() or None


def programs() -> int | None:
    """Programs the process has compiled or loaded so far, over every
    span, as the program counts them; ``None`` where it counts none."""
    try:
        from repro import obs
    except ImportError:
        return None
    return sum(obs.counters()["programs"].values())


def _ns(recs, names) -> int:
    return sum(r[5] - r[4] for r in recs if r[0] in names)


def leaf_ms(recs, name: str, units: int) -> float | None:
    """Milliseconds of the spans called ``name``, per unit; ``None`` where
    no such span was recorded."""
    if not recs or not units or not any(r[0] == name for r in recs):
        return None
    return _ns(recs, (name,)) / 1e6 / units


def untraced_ms(recs, window_s: float, units: int) -> float | None:
    """Milliseconds of the window, per unit, that no leaf span covers."""
    if not recs or not units:
        return None
    return (window_s * 1e3 - _ns(recs, LEAVES) / 1e6) / units
