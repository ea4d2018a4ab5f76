"""The comparison that decides ``correct`` separates sound runs from
broken ones, at sizes a CPU test run can hold.

* The control -- the plain reference computed in bfloat16, one precision
  below the configurations' float32, in the program's place -- fails the
  configured limits.
* The harness, driven past its look for a chip on the CPU, reads a sound
  program as correct and a broken one as not: a tick that returns its
  state unchanged, half of each batch left out, and an answer altered
  where it is produced.
"""
from __future__ import annotations

import copy
import dataclasses
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run  # noqa: E402
from bench.control import control_checks  # noqa: E402

SEED = 2 ** 31 + 77


def small(name: str):
    """The cell with its configuration cut to a CPU test's size; the
    limits stay as configured."""
    _, cfg, mix, entries = run.load_cell(name)
    cfg = copy.deepcopy(cfg)
    cfg["testbeds"] = {"chameleon": cfg["testbeds"]["chameleon"]}
    cfg["horizon_s"] = {"chameleon": 600.0}
    cfg["datasets"] = {k: cfg["datasets"][k] for k in ("small", "mixed")}
    cfg["tools"] = ["wget/curl", "ismail-max-tput", "ME", "EEMT"]
    return cfg, mix, entries


CELLS = tuple(w["name"] for w in run.load_spec()["workloads"])


@pytest.fixture(autouse=True)
def fresh_runners():
    from repro.core import engine
    engine.clear_runner_caches()
    yield
    engine.clear_runner_caches()


def drive(name: str) -> dict:
    cfg, mix, entries = small(name)
    return run.run_cell(name, SEED, 1.0, False, tuple(jax.devices()[:1]),
                        cfg, mix, entries)


@pytest.mark.parametrize("name", CELLS)
def test_control_fails(name):
    cfg, mix, _ = small(name)
    found = control_checks(cfg, mix, SEED)
    assert not all(c.ok for c in found), found


@pytest.mark.parametrize("name", CELLS)
def test_sound_program_is_correct(name):
    out = drive(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


def frozen_tick(monkeypatch):
    from repro.core import engine
    make = engine.make_step_fn

    def make_frozen(*args, **kwargs):
        step = make(*args, **kwargs)

        def frozen(carry, xs):
            _, ys = step(carry, xs)
            return carry, ys
        return frozen
    monkeypatch.setattr(engine, "make_step_fn", make_frozen)


def half_batch(monkeypatch):
    """Only the first half of each batch's lanes is computed; the rest
    are copies of the first lane."""
    from repro.api import scenario
    run_group = scenario._run_group

    def half_group(key, stacked, batch, devices):
        keep = max(batch // 2, 1)
        part = jax.tree.map(lambda x: x[:keep], stacked)
        sim, metrics = run_group(key, part, keep, devices)
        fill = jax.tree.map(
            lambda x: np.concatenate([x, np.repeat(x[:1], batch - keep,
                                                   axis=0)]), (sim, metrics))
        return fill
    monkeypatch.setattr(scenario, "_run_group", half_group)


def altered_answer(monkeypatch):
    """One answer changed where it is produced, in every pass: one grid
    cell's energy, by 5 %."""
    from repro.api import scenario
    post = scenario._postprocess
    target = {}

    def post_altered(sim, metrics, prep):
        r = post(sim, metrics, prep)
        if target.setdefault("cell", prep.name) == prep.name:
            r = dataclasses.replace(r, energy_j=r.energy_j * 1.05)
        return r
    monkeypatch.setattr(scenario, "_postprocess", post_altered)


@pytest.mark.parametrize("fault", [frozen_tick, half_batch, altered_answer],
                         ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", CELLS)
def test_broken_program_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    out = drive(name)
    assert not out["correct"], out["checks"]


FOUR_DEVICES = r'''
import json, sys
import jax
import numpy as np
sys.path.insert(0, sys.argv[1])
from bench import run
from repro.core import engine
sys.path.insert(0, sys.argv[2])
from test_bench_control import small

def drive():
    engine.clear_runner_caches()
    cfg, mix, entries = small("fig2.grid")
    return run.run_cell("fig2.grid", 2 ** 31 + 77, 1.0, False,
                        tuple(jax.devices()[:4]), cfg, mix, entries)

sound = drive()
get = engine.get_sharded_runner

def no_exchange(*args, **kwargs):
    """Each chip's shard stays where it was computed: the host reads the
    first chip's lanes in every chip's place."""
    runner, n = get(*args, **kwargs), len(args[6])
    def local(x):
        x = np.asarray(x)
        return np.concatenate([x[:len(x) // n]] * n)
    return lambda stacked: jax.tree.map(local, runner(stacked))

engine.get_sharded_runner = no_exchange
broken = drive()
print(json.dumps({"sound": sound["correct"], "broken": broken["correct"],
                  "checks": broken["checks"]}))
'''


def test_four_chips_exchange_left_out_is_not_correct():
    """On four (virtual) devices the sweep shards each group across them;
    with the gather of the shards left out, the check fails."""
    import json
    import os
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, "-c", FOUR_DEVICES, str(ROOT),
         str(Path(__file__).parent)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["sound"] and not out["broken"], out
