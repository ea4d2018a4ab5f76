import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Two compiles per cell:

1. FULL program (scan-over-layers where the family supports it) — this is
   the shipped executable: its successful compile proves the sharding
   config, and memory_analysis() proves per-device fit.

2. Depth PROBES — XLA's cost model counts a while-loop (scan) body once,
   so per-layer FLOPs/bytes/collectives are recovered by compiling
   *unrolled* probe programs at full width/batch but reduced depth and
   extrapolating linearly:  cost(L) = cost_out + L * cost_body, solved
   from two probe depths (per layer *type* for heterogeneous stacks).
   Probes compile in seconds because they are 1-4 layers deep.

Usage:
    python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cells, get_config
from repro.distributed.sharding import param_specs, shardings, zero_specs
from repro.launch.hlo_stats import collective_bytes, roofline_terms
from repro.launch.input_specs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.optim import AdamWConfig, OptState
from repro.train import TrainState, init_train_state, make_train_step

TRAIN_MICROBATCHES = 8     # bounds activation memory on the train cells


def _moe_impl(cfg, override=None):
    if override:
        return override
    return "dense" if cfg.moe is not None else "gmm"


def build_cell(cfg, shape_name: str, mesh, moe_impl=None, microbatches=None,
               dp_only: bool = False):
    """Returns (jitted_fn, example_args), ready to .lower(*args)."""
    if dp_only:
        # pure data parallelism: params replicated over 'model', batch
        # sharded over every axis, no TP/SP activity.
        cfg = dataclasses.replace(cfg, seq_parallel=False,
                                  cp_attention=False)
    bundle = build(cfg)
    impl = _moe_impl(cfg, moe_impl)
    inputs, in_shards, kind = input_specs(cfg, shape_name, mesh,
                                          dp_only=dp_only)

    params_shapes = jax.eval_shape(bundle.init_params, jax.random.PRNGKey(0))
    divisor = (1 << 30) if dp_only else 16
    pshard = shardings(mesh, param_specs(params_shapes,
                                         model_divisor=divisor))

    if kind == "train":
        state_shapes = jax.eval_shape(
            lambda: init_train_state(bundle, jax.random.PRNGKey(0)))
        # ZeRO-1: fp32 moments (and the grad accumulator) additionally shard
        # over 'data' — at 30B-MoE scale they dominate per-device memory.
        zspecs = zero_specs(param_specs(params_shapes,
                                        model_divisor=divisor),
                            params_shapes, mesh)
        zshard = shardings(mesh, zspecs)
        sshard = TrainState(
            params=pshard,
            opt=OptState(mu=zshard, nu=zshard,
                         count=NamedSharding(mesh, P())),
            step=NamedSharding(mesh, P()))
        mb = microbatches or TRAIN_MICROBATCHES
        step = make_train_step(bundle, AdamWConfig(), moe_impl=impl,
                               microbatches=mb,
                               grad_acc_specs=zspecs if mb > 1 else None)
        jitted = jax.jit(step, in_shardings=(sshard, in_shards),
                         out_shardings=(sshard, None),
                         donate_argnums=(0,))
        return jitted, (state_shapes, inputs)

    if kind == "prefill":
        def prefill_step(params, batch):
            kw = {k: v for k, v in batch.items() if k != "tokens"}
            logits, _, _ = bundle.forward(params, batch["tokens"],
                                          moe_impl=impl, logits_slice=1, **kw)
            return jnp.argmax(logits, axis=-1)

        out_shard = NamedSharding(mesh, P(None, None))
        jitted = jax.jit(prefill_step, in_shardings=(pshard, in_shards),
                         out_shardings=out_shard)
        return jitted, (params_shapes, inputs)

    # decode: one new token against a populated length-S state
    state_shapes = inputs["state"]
    state_shards = in_shards["state"]
    extra_keys = tuple(k for k in ("enc_out", "mrope_pos") if k in inputs)

    def serve_fn(params, state, tokens, positions, *extra):
        kws = {bundle.state_kwarg: state}
        kws.update(dict(zip(extra_keys, extra)))
        logits, new_state, _ = bundle.forward(
            params, tokens, positions=positions, moe_impl=impl,
            logits_slice=1, **kws)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
        return nxt, new_state

    jitted = jax.jit(
        serve_fn,
        in_shardings=(pshard, state_shards, in_shards["tokens"],
                      in_shards["positions"],
                      *(in_shards[k] for k in extra_keys)),
        out_shardings=(in_shards["tokens"], state_shards),
        donate_argnums=(1,))
    args = (params_shapes, state_shapes, inputs["tokens"],
            inputs["positions"], *(inputs[k] for k in extra_keys))
    return jitted, args


def _compile(cfg, shape_name, mesh, moe_impl, microbatches=None,
             dp_only=False):
    with jax.set_mesh(mesh):
        jitted, args = build_cell(cfg, shape_name, mesh, moe_impl=moe_impl,
                                  microbatches=microbatches, dp_only=dp_only)
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def _costs(compiled):
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll": float(coll["bytes"].get("total", 0)),
        "coll_detail": coll,
    }


def _probe_cfgs(cfg):
    """Probe (cfg, weight) sets per layer type.

    Returns list of (name, [probe_cfg_small, probe_cfg_big], layer_counts)
    such that total = out + sum_i counts_i * body_i, with
    body_i = (cost(big) - cost(small)) / (L_big - L_small)
    and out = cost(small) - L_small * body  (from the first probe pair).
    """
    R = dataclasses.replace
    if cfg.family == "audio":
        return [
            ("dec", [R(cfg, num_layers=1, unroll_layers=True),
                     R(cfg, num_layers=2, unroll_layers=True)],
             cfg.num_layers, (1, 2)),
            ("enc", [R(cfg, num_layers=1, num_encoder_layers=1,
                       unroll_layers=True),
                     R(cfg, num_layers=1, num_encoder_layers=2,
                       unroll_layers=True)],
             cfg.num_encoder_layers, (1, 2)),
        ]
    if cfg.family == "hybrid":
        n_attn = sum(1 for i in range(cfg.num_layers)
                     if cfg.block_pattern[i % len(cfg.block_pattern)] == "local")
        n_rec = cfg.num_layers - n_attn
        return [
            ("rec", [R(cfg, num_layers=1, block_pattern=("rglru",),
                       unroll_layers=True),
                     R(cfg, num_layers=2, block_pattern=("rglru",),
                       unroll_layers=True)],
             n_rec, (1, 2)),
            ("attn", [R(cfg, num_layers=1, block_pattern=("local",),
                        unroll_layers=True),
                      R(cfg, num_layers=2, block_pattern=("local",),
                        unroll_layers=True)],
             n_attn, (1, 2)),
        ]
    return [("layer", [R(cfg, num_layers=1, unroll_layers=True),
                       R(cfg, num_layers=2, unroll_layers=True)],
             cfg.num_layers, (1, 2))]


def probe_extrapolate(cfg, shape_name, mesh, moe_impl, dp_only=False):
    """Per-device (flops, hbm_bytes, collective_bytes) extrapolated to the
    full depth from unrolled shallow probes."""
    probes = _probe_cfgs(cfg)
    # base "out" term from the first probe family
    total = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
    out_term = None
    detail = {}
    for name, (small, big), count, (ls, lb) in probes:
        # microbatches=1: no grad-accumulation while-loop in the probes, so
        # the cost model sees the whole batch regardless of XLA's unrolling
        # decisions for the full program.
        cs = _costs(_compile(small, shape_name, mesh, moe_impl,
                             microbatches=1, dp_only=dp_only))
        cb = _costs(_compile(big, shape_name, mesh, moe_impl,
                             microbatches=1, dp_only=dp_only))
        body = {k: (cb[k] - cs[k]) / (lb - ls)
                for k in ("flops", "bytes", "coll")}
        detail[name] = {"per_layer": body, "count": count}
        if out_term is None:
            out_term = {k: cs[k] - ls * body[k]
                        for k in ("flops", "bytes", "coll")}
        for k in total:
            total[k] += count * max(body[k], 0.0)
    for k in total:
        total[k] += max(out_term[k], 0.0)
    detail["out"] = out_term
    return total, detail


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             moe_impl=None, extra_opts=None, verbose=True,
             skip_probes=False):
    opts = extra_opts or {}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    cfg = get_config(arch)
    ovr = {k: v for k, v in opts.items()
           if k in {f.name for f in dataclasses.fields(cfg)}}
    if ovr:
        cfg = dataclasses.replace(cfg, **ovr)

    dp_only = bool(opts.get("dp_only"))
    # 1. full program: sharding proof + memory
    t0 = time.time()
    compiled = _compile(cfg, shape_name, mesh, moe_impl,
                        microbatches=opts.get("microbatches"),
                        dp_only=dp_only)
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    full_cost = _costs(compiled)

    # 2. probes: exact per-layer roofline terms
    if skip_probes:
        total, detail = full_cost, {"note": "scan-body counted once"}
    else:
        total, detail = probe_extrapolate(cfg, shape_name, mesh, moe_impl,
                                          dp_only=dp_only)

    terms = roofline_terms(total["flops"], total["bytes"], total["coll"],
                           chips)
    sh = SHAPES[shape_name]
    mult = 6 if sh["kind"] == "train" else 2
    model_flops = mult * cfg.active_param_count() * _tokens(shape_name)

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "kind": sh["kind"],
        "compile_s": round(t_compile, 1),
        "flops_per_device": total["flops"],
        "hbm_bytes_per_device": total["bytes"],
        "coll_bytes_per_device": total["coll"],
        "probe_detail": {k: v for k, v in detail.items()},
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_per_device": (mem.argument_size_in_bytes
                                + mem.temp_size_in_bytes),
        },
        "roofline": terms,
        "model_flops": model_flops,
        "useful_flops_ratio": (model_flops / (total["flops"] * chips)
                               if total["flops"] else 0.0),
    }
    if verbose:
        slim = {k: v for k, v in result.items() if k != "probe_detail"}
        print(json.dumps(slim, indent=1))
    return result


def _tokens(shape_name: str) -> int:
    sh = SHAPES[shape_name]
    if sh["kind"] in ("train", "prefill"):
        return sh["seq_len"] * sh["global_batch"]
    return sh["global_batch"]          # decode: one token per sequence


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--moe-impl", default=None)
    ap.add_argument("--opt", action="store_true",
                    help="optimized config: a2a MoE + cp_attention "
                         "(the EXPERIMENTS.md §Perf configuration)")
    ap.add_argument("--skip-probes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    todo = []
    if args.all:
        for a in ARCHS:
            for s in cells(a):
                todo.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch, shape in todo:
        tag = f"{arch}_{shape}_{'2x16x16' if args.multi_pod else '16x16'}"
        print(f"=== {tag} ===", flush=True)
        try:
            moe_impl = args.moe_impl or ("a2a" if args.opt else None)
            extra = {"cp_attention": True} if args.opt else None
            res = run_cell(arch, shape, multi_pod=args.multi_pod,
                           moe_impl=moe_impl, extra_opts=extra,
                           skip_probes=args.skip_probes)
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(res, f, indent=1)
        except Exception as e:
            traceback.print_exc()
            failures.append((tag, str(e)))
    if failures:
        print("FAILURES:")
        for t, e in failures:
            print(" ", t, e[:200])
        raise SystemExit(1)
    print(f"all {len(todo)} cells compiled OK")


if __name__ == "__main__":
    main()
