"""The benchmark's harness on the CPU: every BENCHMARK.json entry resolves
to its files by name, names and units keep to their characters, the
entry point refuses to run without a TPU, and the traffic generators are
pure functions of the seed."""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generators  # noqa: E402
from bench import layers as readers  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CONFIGS = {c["name"]: c for c in SPEC["configs"]}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= SPEC["run_seconds"] <= 51
    for path in SPEC["paths"]:
        assert (ROOT / path).is_dir()
        assert not path.startswith("/") and ".." not in path.split("/")


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda c: c["name"])
def test_cell_resolves(cell):
    import importlib
    cfg_entry = CONFIGS[cell["config"]]
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
    assert hasattr(importlib.import_module(f"bench.{cfg['kind']}"),
                   "Workload")
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    e2e = [m["name"] for m in SPEC["end_to_end"]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m for m in SPEC["per_layer"]
             if cell["name"] in m.get("workloads", [cell["name"]])]
    assert layer and all(m["moves"] in e2e for m in layer)


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_reader_resolves(metric):
    assert callable(readers.load(metric["name"]))


def test_names_and_units():
    names = ([c["name"] for c in SPEC["configs"]]
             + [w["name"] for w in SPEC["workloads"]]
             + [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
             + [w["traffic"] for w in SPEC["workloads"]])
    for name in names:
        assert NAME.match(name), name
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25


def test_entry_keys():
    """Each entry holds just the keys of its kind (a metric may add
    ``workloads``), and every text field is one line of 1-200 characters."""
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for kind, want in keys.items():
        for entry in SPEC[kind]:
            assert set(entry) - {"workloads"} == want, entry
            for field in ("why", "layer", "source"):
                text = entry.get(field, "x")
                assert 1 <= len(text) <= 200 and "\n" not in text
                assert "\t" not in text
    for c in SPEC["configs"]:
        assert c["file"].startswith("bench/") and (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == \
            c["reduced"]


def test_no_tpu_no_result():
    """Without a TPU the entry point exits non-zero and prints nothing on
    standard output: there is no CPU fallback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


SEEDS = (0, 7, 2 ** 31 + 11, -3)
MIXES = sorted({w["traffic"] for w in SPEC["workloads"]})


@pytest.mark.parametrize("mix", MIXES)
def test_mix_names_its_generator(mix):
    """A mix is data: its generator is found by the name it gives."""
    data = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                      .read_text())
    assert data["name"] == mix
    assert callable(generators.load(data["generator"]).plan)


@pytest.mark.parametrize("seed", SEEDS)
def test_grid_passes_follow_the_seed(seed):
    """The run seed orders the cells and nothing else: every cell once,
    nothing overridden, so the same work in another order."""
    gen = generators.load("grid_passes")
    a, b = gen.plan(seed, {}, 72), gen.plan(seed, {}, 72)
    c = gen.plan(seed + 1, {}, 72)
    assert a == b and a != c
    assert sorted(k for k, _ in a) == list(range(72))
    assert all(over == {} for _, over in a)


def test_grid_takes_a_plan_by_name(monkeypatch):
    """The grid workload runs what the mix's generator plans, overrides
    included, and hands the reference the same schedules."""
    import types
    sys.path.insert(0, str(ROOT / "src"))
    from bench import grid, run
    _, cfg, _, _ = run.load_cell("fig2.grid")
    steps = int(round(cfg["horizon_s"]["cloudlab"] / cfg["dt"]))
    half = np.full(steps, 0.5, np.float32)
    fake = types.SimpleNamespace(
        plan=lambda seed, mix, n: [(n - 1, {}), (30, {"bw_schedule": half})])
    monkeypatch.setattr(generators, "load", lambda name: fake)
    w = grid.GridWorkload(cfg, {"generator": "fake"}, 1, ())
    assert len(w.cells) == 2
    assert w.cells[0].labels == w.exp.cells()[-1].labels
    assert w.cells[1].scenario.bw_schedule is half
    assert w.spec[1]["bw"] is half and w.spec[1]["horizon_s"] == \
        cfg["horizon_s"][w.cells[1].labels["testbed"]]
    assert np.all(w.spec[0]["bw"] == 1.0)
    fake.plan = lambda seed, mix, n: [(0, {"total_s": 1.0})]
    with pytest.raises(ValueError, match="reference follows only"):
        grid.GridWorkload(cfg, {"generator": "fake"}, 1, ())
