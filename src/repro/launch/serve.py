"""Serving launcher: batched greedy decoding against a KV cache / recurrent
state, with the production-mesh sharding when requested.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-7b --smoke \
        --batch 4 --prompt-len 16 --new-tokens 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, get_smoke_config
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.serve import make_decode_step, make_prefill


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--tp", type=int, default=1)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    bundle = build(cfg)
    mesh = make_host_mesh(model=args.tp)

    with jax.set_mesh(mesh):
        params = bundle.init_params(jax.random.PRNGKey(0))
        B, T, N = args.batch, args.prompt_len, args.new_tokens
        prompt = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                    cfg.vocab_size)
        state = bundle.init_decode_state(B, T + N)

        prefill = jax.jit(make_prefill(bundle))
        step = jax.jit(make_decode_step(bundle))

        kw = {}
        if cfg.family == "audio":
            kw["enc_out"] = jax.random.normal(
                jax.random.PRNGKey(2),
                (B, cfg.encoder_positions, cfg.d_model), jnp.bfloat16)

        logits, state = prefill(params, state, prompt, **kw)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks = [tok]
        t0 = time.perf_counter()
        for i in range(N - 1):
            pos = jnp.full((B, 1), T + i, jnp.int32)
            tok, _, state = step(params, state, tok, pos)
            toks.append(tok)
        jax.block_until_ready(tok)
        dt = time.perf_counter() - t0

    print(f"{cfg.name}: {B * (N - 1) / dt:.1f} tok/s batched "
          f"({dt / max(N - 1, 1) * 1e3:.2f} ms/step)")


if __name__ == "__main__":
    main()
