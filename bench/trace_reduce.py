"""From a profiler trace to the numbers the layer readers use.

A trace is reduced to plain data first (:func:`from_xplane`): for each
device plane its op and program-execution intervals, and the host's
events, all in nanoseconds on the trace's one clock.  The same structure is
what the tests load from a small recorded trace, so every reduction below
is checked on real chip data without a chip.

* busy time: the union of the device's op intervals inside a window;
* program executions: the ``XLA Modules`` events that start in it;
* top ops: self time (an op's duration less the part of it that ops
  starting inside it cover), summed by op name, so that the self times
  of a window add up to its busy time;
* idle gaps: the stretches of the window in which no op ran, each labelled
  by the innermost host event that covers its middle.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"


def _short(name: str) -> str:
    """An op's HLO text up to its ``=``: ``%fusion.66``."""
    return name.split(" = ", 1)[0][:120]


def from_xplane(path: str) -> dict:
    """Plain data of one ``.xplane.pb``: ``devices`` maps each TPU plane
    to ``ops`` and ``modules`` lists of ``[name, start_ns, end_ns]``;
    ``host`` lists every host event as ``[name, start_ns, end_ns, line]``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is None:
                    continue
                short = _short if key == "ops" else (lambda n: n)
                dev[key] = [[short(e.name), e.start_ns,
                             e.start_ns + e.duration_ns]
                            for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                out["host"].extend([e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, line.name]
                                   for e in line.events)
    return out


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def window(trace: dict, span: str = WINDOW_SPAN) -> tuple[float, float]:
    """(start, end) in ns of the host span that marks the traced window."""
    spans = [e for e in trace["host"] if e[0] == span]
    if not spans:
        raise ValueError(f"the trace holds no {span!r} span")
    return min(e[1] for e in spans), max(e[2] for e in spans)


def _clip(events: list, lo: float, hi: float) -> np.ndarray:
    iv = np.array([[s, e] for _, s, e in events], np.float64).reshape(-1, 2)
    iv = np.clip(iv, lo, hi)
    return iv[iv[:, 1] > iv[:, 0]]


def _union(iv: np.ndarray) -> np.ndarray:
    """Disjoint sorted intervals covering the same points."""
    if len(iv) == 0:
        return iv
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(new)
    stops = ends[np.r_[last[1:] - 1, len(iv) - 1]]
    return np.stack([starts, stops], axis=1)


def busy_ns(dev: dict, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] in which some op ran on the device."""
    u = _union(_clip(dev["ops"], lo, hi))
    return float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0


def executions(dev: dict, lo: float, hi: float) -> int:
    """Programs the device started in [lo, hi)."""
    return sum(1 for _, s, _ in dev["modules"] if lo <= s < hi)


def top_ops(devs: list, lo: float, hi: float, k: int = 10) -> list:
    """[[op name, self seconds summed over devices], ...], longest first.
    Self time leaves out the time of ops an op encloses (a loop and its
    body), so no second is counted twice."""
    total: dict = defaultdict(float)
    for dev in devs:
        ops = sorted((s, -e, name) for name, s, e in dev["ops"]
                     if s < hi and e > lo)
        stack: list = []          # [end, name, self]
        for s, neg_e, name in ops:
            e = -neg_e
            while stack and stack[-1][0] <= s:
                end, nm, own = stack.pop()
                total[nm] += own
            s_c, e_c = max(s, lo), min(e, hi)
            if stack:             # the part inside the enclosing op
                stack[-1][2] -= min(e_c, stack[-1][0]) - s_c
            stack.append([e, name, e_c - s_c])
        for end, nm, own in stack:
            total[nm] += own
    top = sorted(total.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / 1e9] for name, ns in top]


def idle_gaps(dev: dict, host: list, lo: float, hi: float,
              k: int = 10) -> list:
    """[[label, seconds], ...]: the longest stretches of [lo, hi] with no op
    on the device, labelled ``outer/inner`` by the outermost and innermost
    host events that cover the middle of the gap."""
    u = _union(_clip(dev["ops"], lo, hi))
    edges = np.concatenate([[lo], u.ravel(), [hi]]).reshape(-1, 2)
    gaps = [(b - a, a, b) for a, b in edges if b > a]
    gaps.sort(reverse=True)
    out = []
    for length, a, b in gaps[:k]:
        mid = (a + b) / 2
        cover = sorted((e - s, name) for name, s, e, _ in host
                       if s <= mid <= e and name != WINDOW_SPAN)
        if cover:
            inner, outer = cover[0][1], cover[-1][1]
            label = inner if inner == outer else f"{outer}/{inner}"
        else:             # host code outside any traced call
            label = "(no host span)"
        out.append([label[:160], float(length) / 1e9])
    return out


def reduce(trace: dict, span: str = WINDOW_SPAN) -> dict:
    """Everything the readers and the breakdown use, for one window."""
    lo, hi = window(trace, span)
    devs = [trace["devices"][n] for n in sorted(trace["devices"])]
    busy = [busy_ns(d, lo, hi) / 1e9 for d in devs]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "executions": [executions(d, lo, hi) for d in devs],
        "device_ops": top_ops(devs, lo, hi),
        "idle_gaps": idle_gaps(devs[0], trace["host"], lo, hi) if devs
        else [],
    }
