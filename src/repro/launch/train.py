"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 50 --batch 8 --seq 128

On a real pod this runs under one process per host with
``jax.distributed.initialize()`` (multi-host), the production mesh from
mesh.py, and the full config; on a dev box it uses the local devices and
(optionally) the smoke config.  Either way the flow is identical:
mesh -> sharded TrainState -> SLA-tuned ingest -> fault-tolerant trainer.
"""
from __future__ import annotations

import argparse

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, get_smoke_config
from repro.core.types import SLA, SLAPolicy
from repro.data import SyntheticSource, batches
from repro.distributed.sharding import param_specs, shardings
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import build
from repro.optim import AdamWConfig, OptState
from repro.train import TrainState, init_train_state
from repro.train.trainer import TrainerConfig, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (dev boxes)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tp", type=int, default=1,
                    help="model-parallel degree of the host mesh")
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 pod mesh (requires 256 devices)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--sla", default="max_tput",
                    choices=["max_tput", "min_energy"])
    ap.add_argument("--lr", type=float, default=3e-4)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    bundle = build(cfg)

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = make_host_mesh(model=args.tp)
    print(f"mesh: {dict(mesh.shape)}  arch: {cfg.name} "
          f"({cfg.param_count() / 1e6:.1f}M params)")

    with jax.set_mesh(mesh):
        state = init_train_state(bundle, jax.random.PRNGKey(0))
        pspecs = param_specs(state.params,
                             model_divisor=mesh.shape.get("model", 1))
        pshard = shardings(mesh, pspecs)
        sshard = TrainState(params=pshard,
                            opt=OptState(mu=pshard, nu=pshard,
                                         count=NamedSharding(mesh, P())),
                            step=NamedSharding(mesh, P()))
        state = jax.device_put(state, sshard)

        sla = SLA(policy=SLAPolicy.MAX_THROUGHPUT if args.sla == "max_tput"
                  else SLAPolicy.MIN_ENERGY, timeout_s=0.5, max_ch=8)
        data = batches(SyntheticSource(cfg.vocab_size, 1 << 16),
                       batch=args.batch, seq=args.seq, tuned=True, sla=sla)

        # trainer re-inits unsharded if no checkpoint; hand it ours instead
        def hooked_train():
            opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                                  total_steps=args.steps)
            tcfg = TrainerConfig(total_steps=args.steps,
                                 ckpt_dir=args.ckpt_dir, ckpt_every=50,
                                 log_every=10,
                                 microbatches=args.microbatches)
            return train(bundle, opt_cfg, data, tcfg)

        _, report = hooked_train()
    print(f"final loss {report.final_loss:.4f} over {report.steps_run} steps; "
          f"stragglers={report.straggler_steps}")


if __name__ == "__main__":
    main()
