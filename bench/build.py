"""The program's inputs, built from a configuration's JSON dicts.

Only the program's public types and registry are used: the network
profile, dataset specs, CPU profile and controller a user would construct.
"""
from __future__ import annotations

from repro import api
from repro.core.types import CpuProfile, DatasetSpec, NetworkProfile

TUNER_TOOLS = ("ME", "EEMT")
SLA_KEYS = ("alpha", "beta", "delta_ch", "max_ch", "timeout_s", "max_load",
            "min_load")


def profile(name: str, path: dict) -> NetworkProfile:
    return NetworkProfile(name=name, **path)


def datasets(specs: list) -> tuple:
    return tuple(DatasetSpec(**s) for s in specs)


def cpu(spec: dict) -> CpuProfile:
    return CpuProfile(**dict(spec, freq_levels_ghz=tuple(
        spec["freq_levels_ghz"])))


def controller(tool: str, tuner: dict):
    """A registry controller: the paper's tuners with the configuration's
    hyper-parameters, the static baselines by name."""
    if tool in TUNER_TOOLS:
        return api.make_controller(tool, **{k: tuner[k] for k in SLA_KEYS})
    return tool
