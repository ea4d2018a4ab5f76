"""The control: the plain reference computed one precision below the
configuration's float32 (bfloat16), put in the program's place and judged
by the same comparison.  It has to come out not correct.

    python3 -m bench.control --workload <cell> --seeds 1 2 3

It compares one whole pass of the cell's grid.  The control runs on the default device, the reference it is judged against on
the host CPU; it prints one JSON line per seed with each compared number
and its limit.
"""
from __future__ import annotations

import argparse
import importlib
import json
import sys

from .run import ROOT, load_cell


def control_checks(cfg: dict, mix: dict, seed: int) -> list:
    import jax
    import jax.numpy as jnp

    kind = importlib.import_module(f"bench.{cfg['kind']}")
    workload = kind.Workload(cfg, mix, seed, (jax.devices("cpu")[0],))
    # On the accelerator where there is one: bfloat16 is native there.
    return workload.compare(workload.reference_answers(jnp.bfloat16,
                                                       jax.devices()[0]))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    _, cfg, mix, _ = load_cell(args.workload)
    for seed in args.seeds:
        found = control_checks(cfg, mix, seed)
        print(json.dumps({"seed": seed, "fails": not all(c.ok for c in found),
                          "checks": {c.name: [c.value, c.limit]
                                     for c in found}}), flush=True)


if __name__ == "__main__":
    main()
