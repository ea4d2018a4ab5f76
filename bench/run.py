"""Run one benchmark cell on the accelerator this process finds.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

from the root of a checkout.  Everything the cell needs is found by name
from ``BENCHMARK.json``: its configuration file, ``traffic/<mix>.json``,
and with ``--trace 1`` one reader per per-layer metric in
``layers/<metric>.py``.  The configuration's ``kind`` names the workload
module (``grid.py``), the mix's ``generator`` its traffic generator
(``generators/<generator>.py``).

Set-up (``setup_s``) runs from process start to the window: device
start-up, inputs from the seed, and a warm-up over the cell's own shapes
(compiled, or loaded from the persistent compilation cache kept in the
checkout).  With ``--trace 0`` the window is measured on the host clock;
with ``--trace 1`` a shorter window runs under the profiler and the
per-layer metrics are read from its trace.  Either way the answers of the
window are then compared with the plain reference (``reference.py``), and
the last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``, each compared number beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result: there is no CPU fallback.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
HERE = Path(__file__).resolve().parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_traces"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def fail(msg: str) -> None:
    raise SystemExit(f"bench.run: {msg}")


class CompileClock:
    """Counts compiles (persistent-cache hits apart) and the seconds JAX
    spends tracing, lowering and compiling or loading programs, from JAX's
    own events (the compile clock of ``chip_smoke.py``)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              COMPILE_EVENT)

    def __init__(self, jax):
        self.seconds = 0.0
        self.requests = 0            # compiles and cache loads
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == COMPILE_EVENT:
            self.requests += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.hits += 1

    @property
    def compiles(self) -> int:
        return self.requests - self.hits


class Tracer:
    """The profiler over the traced window, which the ``bench.window``
    host span marks; usable as a context manager or by start / stop."""

    def __init__(self, jax, log_dir: Path):
        self.jax, self.log_dir = jax, log_dir
        self.span = None

    def start(self) -> None:
        shutil.rmtree(self.log_dir, ignore_errors=True)
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        self.jax.profiler.start_trace(str(self.log_dir),
                                      profiler_options=opts)
        self.span = self.jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()

    def stop(self) -> None:
        self.span.__exit__(None, None, None)
        t = time.perf_counter()
        self.jax.profiler.stop_trace()
        print(f"trace written in {time.perf_counter() - t:.3f} s",
              file=sys.stderr, flush=True)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


def load_spec() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str) -> tuple[dict, dict, dict, list]:
    """(cell, configuration, traffic mix, metric entries) of one
    ``BENCHMARK.json`` workload; the entries are the end-to-end and
    per-layer metrics the cell reports."""
    spec = load_spec()
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        fail(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    cfg = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    entries = [m for m in spec["end_to_end"] + spec["per_layer"]
               if name in m.get("workloads", [name])]
    return cell, cfg, mix, entries


def use_compile_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips(jax, n: int) -> tuple:
    """The first ``n`` TPU chips, or exit: there is no CPU fallback."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        fail(f"no TPU found: {e}")
    if devices[0].platform != "tpu":
        fail(f"no TPU found (JAX sees {devices[0].platform!r}); there is "
             f"no CPU fallback")
    if len(devices) < n:
        fail(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return tuple(devices[:n])


def device_info(devices: tuple) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             devices: tuple, cfg: dict, mix: dict, entries: list) -> dict:
    """Set up, measure (or trace), check; the result object.  Tests pass
    their own (smaller) ``cfg`` and devices."""
    import jax

    from . import checks, rooflines, trace_reduce
    from . import layers as readers

    units = {m["name"]: m["unit"] for m in entries}
    peaks = rooflines.peaks(devices[0].device_kind) if trace else None
    clock = CompileClock(jax)
    kind = importlib.import_module(f"bench.{cfg['kind']}")
    t_start = time.perf_counter()
    workload = kind.Workload(cfg, mix, seed, devices)
    t_inputs = time.perf_counter()
    workload.warm()
    setup_s = time.perf_counter() - T0
    print(f"setup_s={setup_s:.3f} (start {t_start - T0:.3f}, inputs "
          f"{t_inputs - t_start:.3f}, warm-up {T0 + setup_s - t_inputs:.3f})"
          f" compile_clock_s={clock.seconds:.3f} compiles={clock.compiles}"
          f" cache_hits={clock.hits}", file=sys.stderr, flush=True)

    before = clock.compiles
    result = {}
    if trace:
        tracer = Tracer(jax, TRACE_DIR / name)
        t = [time.perf_counter()]
        counters = workload.traced(seconds, tracer)
        t.append(time.perf_counter())
        events = trace_reduce.from_xplane(
            trace_reduce.find_xplane(str(tracer.log_dir)))
        t.append(time.perf_counter())
        reduced = trace_reduce.reduce(events)
        t.append(time.perf_counter())
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
        print(f"traced run {t[1] - t[0]:.3f} s, trace read {t[2] - t[1]:.3f}"
              f" s, reduced {t[3] - t[2]:.3f} s", file=sys.stderr,
              flush=True)
        ctx = {"trace": reduced, "counters": counters, "peaks": peaks}
        metrics = {}
        for m in entries:
            value = readers.load(m["name"])(ctx) if "layer" in m else None
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        busy = reduced["busy_s"]
        extra = {"busy_s": sum(busy) / max(len(busy), 1),
                 "window_s": reduced["window_s"]}
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in workload.measure(seconds).items()}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        extra = {}
    print(f"compiles_in_window={clock.compiles - before}", file=sys.stderr,
          flush=True)
    device = dict(device_info(devices), **extra)

    t_check = time.perf_counter()
    found = workload.check()
    attempted, failed = workload.attempted()
    print(f"check {time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    for c in found:
        print(f"check {c.name} value={c.value!r} limit={c.limit!r} "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    return {"correct": all(c.ok for c in found), "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device,
            **result, "checks": checks.format_checks(found)}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"{ROOT} holds no src/repro: the system under test is missing")
    sys.path.insert(0, str(ROOT / "src"))
    cell, cfg, mix, entries = load_cell(args.workload)
    # libtpu would otherwise log to a fixed /tmp path shared by processes.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # The reference runs on the host CPU beside the chips.
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax

    use_compile_cache(jax)
    devices = chips(jax, cell["chips"])
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   devices, cfg, mix, entries)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
