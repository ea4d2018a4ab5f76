"""Microbenchmarks: engine throughput, vmap sweep scaling, kernel timings.

These measure the FRAMEWORK itself (CPU wall time; the kernels run in
interpret mode, so their numbers are correctness-path timings, not TPU
performance — TPU projections live in the roofline analysis).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro import api
from repro.core import CHAMELEON, MIXED, CpuProfile

from .common import emit

CPU = CpuProfile()


def bench_engine(rows=None):
    """One full simulated transfer (jit warm) — engine steps/second.

    Uses the full-horizon reference runner (``early_exit=False``) so the
    step count in the steps/s metric is the step count actually executed;
    the default early-exit runner stops ~1 chunk past completion and would
    inflate the number.
    """
    import jax
    import numpy as np

    from repro.core import engine

    n_steps = 6000
    ctrl = api.make_controller("eemt", max_ch=64)
    ci = ctrl.init(MIXED, CHAMELEON, CPU)
    inp = jax.tree.map(np.asarray,
                       engine.ScanInputs.from_init(ci, CHAMELEON))
    runner = engine.get_runner(ctrl.code(), api.as_environment(None).code(),
                               CPU, n_steps, 0.1, 10,
                               batched=False, early_exit=False)
    jax.block_until_ready(runner(inp))                        # warm
    t0 = time.perf_counter()
    n = 3
    for _ in range(n):
        jax.block_until_ready(runner(inp))
    dt = (time.perf_counter() - t0) / n
    emit("micro/engine_transfer", dt, f"{n_steps / dt:.0f}steps_per_s")


def bench_engine_executors(bench=None, n_steps=6000):
    """Engine ticks/second per executor (jit warm, best-of-3).

    One unbatched transfer inflated so it never completes inside the
    horizon: every executor then executes exactly ``n_steps`` ticks
    (the pallas kernel early-exits internally, so an incomplete transfer
    is what makes the tick counts comparable).  Records
    ``engine_<executor>_ticks_per_sec`` into ``bench`` — the ``_per_sec``
    suffix is what the CI perf gate tracks (benchmarks/compare.py).
    Pallas runs in interpret mode on CPU: its number is a correctness-path
    timing, not kernel performance.
    """
    import numpy as np

    from repro.core import engine

    ctrl = api.make_controller("eemt", max_ch=64)
    ci = ctrl.init(MIXED, CHAMELEON, CPU)
    inp = jax.tree.map(np.asarray,
                       engine.ScanInputs.from_init(ci, CHAMELEON))
    inp = inp._replace(total_mb=inp.total_mb * 1e6)   # never completes
    env = api.as_environment(None).code()
    for ex in ("reference", "blocked", "pallas"):
        runner = engine.get_runner(ctrl.code(), env, CPU, n_steps, 0.1, 10,
                                   batched=False, early_exit=False,
                                   executor=ex)
        jax.block_until_ready(runner(inp))                    # warm
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(runner(inp))
            best = min(best, time.perf_counter() - t0)
        tps = n_steps / best
        emit(f"micro/engine_ticks_{ex}", best, f"{tps:.0f}ticks_per_s")
        if bench is not None:
            bench[f"engine_{ex}_ticks_per_sec"] = tps


def bench_vmap_sweep(rows=None):
    """Parameter sweep via vmap: K simultaneous simulations in one XLA call
    (the JAX-native replacement for the paper's sequential experiments)."""
    from repro.core import engine

    K = 64
    n_steps = 2000
    ctrl = api.make_controller("eemt", max_ch=64)
    ci = ctrl.init(MIXED, CHAMELEON, CPU)
    base = engine.ScanInputs.from_init(ci, CHAMELEON)
    # Full-horizon reference: every lane really executes n_steps ticks, so
    # the sim_steps_per_s metric divides by the work actually done.
    core = engine.build_core(ctrl.code(), api.as_environment(None).code(),
                             CPU, n_steps=n_steps, dt=0.1,
                             ctrl_every=10, early_exit=False)

    def one(num_ch0):
        ts0 = base.state0._replace(num_ch=num_ch0, prev_num_ch=num_ch0)
        sim, _, _ = core(base._replace(state0=ts0))
        return sim.energy_j

    sweep = jax.jit(jax.vmap(one))
    ch0 = jnp.linspace(1.0, 64.0, K)
    sweep(ch0).block_until_ready()                            # warm
    t0 = time.perf_counter()
    sweep(ch0).block_until_ready()
    dt = time.perf_counter() - t0
    emit("micro/vmap_sweep_64cfg", dt,
         f"{K * n_steps / dt:.0f}sim_steps_per_s")


def bench_kernels(rows=None):
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.rglru import rglru
    from repro.kernels.rwkv6 import wkv

    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, T, H, hd = 1, 512, 4, 64
    q = jax.random.normal(ks[0], (B, T, H, hd))
    k = jax.random.normal(ks[1], (B, T, 2, hd))
    v = jax.random.normal(ks[2], (B, T, 2, hd))

    def run_fa():
        return flash_attention(q, k, v, interpret=True)

    run_fa()
    t0 = time.perf_counter()
    run_fa()
    dt = time.perf_counter() - t0
    flops = 2 * 2 * B * H * T * T * hd * 0.5
    emit("micro/flash_attention_512", dt, f"{flops / dt / 1e9:.2f}GFLOPs_interp")

    r = jax.random.normal(ks[0], (B, 128, H, hd)) * 0.4
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, 128, H, hd))) * 0.4 + 0.5
    u = jax.random.normal(ks[4], (H, hd)) * 0.2
    kk = jax.random.normal(ks[1], (B, 128, H, hd)) * 0.4
    vv = jax.random.normal(ks[2], (B, 128, H, hd)) * 0.4
    wkv(r, kk, vv, w, u, interpret=True)
    t0 = time.perf_counter()
    wkv(r, kk, vv, w, u, interpret=True)
    emit("micro/wkv_128", time.perf_counter() - t0, "interp")

    a = jax.nn.sigmoid(jax.random.normal(ks[0], (2, 128, 512))) * 0.4 + 0.5
    b = jax.random.normal(ks[1], (2, 128, 512)) * 0.1
    rglru(a, b, interpret=True)
    t0 = time.perf_counter()
    rglru(a, b, interpret=True)
    emit("micro/rglru_128", time.perf_counter() - t0, "interp")


def bench_train_smoke(rows=None):
    """Wall time of one smoke-model train step (jit warm)."""
    from repro.configs import get_smoke_config
    from repro.models import build
    from repro.optim import AdamWConfig
    from repro.train import init_train_state, make_train_step

    cfg = get_smoke_config("qwen2-0.5b")
    bundle = build(cfg)
    state = init_train_state(bundle, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(bundle, AdamWConfig()))
    batch = {"tokens": jnp.zeros((4, 64), jnp.int32),
             "labels": jnp.zeros((4, 64), jnp.int32)}
    state, _ = step(state, batch)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    state, m = step(state, batch)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    emit("micro/train_step_smoke", dt, f"loss={float(m['loss']):.3f}")


def run(rows=None, bench=None, smoke=False):
    """``smoke=True`` (CI bench-smoke) runs only the gated per-executor
    engine record on a shorter horizon; the full micro suite is the
    default."""
    if smoke:
        bench_engine_executors(bench, n_steps=2000)
        return
    bench_engine(rows)
    bench_engine_executors(bench)
    bench_vmap_sweep(rows)
    bench_kernels(rows)
    bench_train_smoke(rows)


if __name__ == "__main__":
    run()
