"""Controller ``init`` runs on the host: Algorithm 1, the tuner state and the
static baselines are numpy, so preparing a scenario touches no device, and
the host arithmetic rounds exactly as the float32 ``jnp`` expressions do."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api, learn
from repro.api.scenario import _prepare
from repro.core import heuristics
from repro.core.types import (CHAMELEON, LARGE_FILES, MEDIUM_FILES, MIXED,
                              SLA, SMALL_FILES, TESTBEDS, CpuProfile,
                              DatasetSpec, NetworkProfile, SLAPolicy)

CPU = CpuProfile()
SLAS = (SLA(policy=SLAPolicy.MIN_ENERGY),
        SLA(policy=SLAPolicy.MAX_THROUGHPUT),
        SLA(policy=SLAPolicy.TARGET_THROUGHPUT, target_tput_mbps=500.0),
        SLA(policy=SLAPolicy.ISMAIL_TARGET))


def _jnp_split_large_files(spec, bdp_mb):
    if spec.avg_file_mb > bdp_mb and bdp_mb > 0:
        par = float(int(jnp.ceil(spec.avg_file_mb / bdp_mb)))
        return DatasetSpec(name=spec.name,
                           num_files=int(spec.num_files * par),
                           total_mb=spec.total_mb,
                           avg_file_mb=spec.avg_file_mb / par,
                           std_file_mb=spec.std_file_mb / par), par
    return spec, 1.0


def _jnp_initialize(specs, profile, cpu, sla):
    """Algorithm 1 in eager float32 ``jnp``: the oracle for the host's
    numpy arithmetic."""
    bdp = profile.bdp_mb
    chunked, par = zip(*[_jnp_split_large_files(s, bdp) for s in specs])
    pp = [min(max(1.0, float(jnp.ceil(bdp / max(s.avg_file_mb, 1e-6)))),
              128.0) for s in chunked]
    goal_mbps = profile.bandwidth_mbps
    if sla.policy == SLAPolicy.TARGET_THROUGHPUT and sla.target_tput_mbps > 0:
        goal_mbps = min(goal_mbps, sla.target_tput_mbps)
    tput_channel = profile.avg_window_mb / profile.rtt_s
    num_channels = float(jnp.ceil(goal_mbps / max(tput_channel, 1e-6)))
    sizes = jnp.array([s.total_mb for s in chunked], jnp.float32)
    weights = sizes / jnp.maximum(jnp.sum(sizes), 1e-6)
    cc = jnp.maximum(jnp.ceil(weights * num_channels), 1.0)
    cores = 1 if sla.policy == SLAPolicy.MIN_ENERGY else cpu.num_cores
    return (np.asarray(pp, np.float32), np.asarray(par, np.float32),
            np.asarray(cc, np.float32), cores, 0), tuple(chunked)


# Quotients of 3 + 1e-8: float32 rounds each onto 3, so its ``ceil`` is 3
# where float64 ``math.ceil`` gives 4.  (case: profile, datasets, the
# field Algorithm 1 rounds there, its float64 quotient)
_Q = 3.0 + 1e-8
_EDGE = NetworkProfile("edge", bandwidth_mbps=2.0 * _Q, rtt_s=0.5,
                       avg_window_mb=1.0)       # tputChannel = 2 MB/s
_NEAR_PP = DatasetSpec("near", 100, 1000.0, CHAMELEON.bdp_mb / _Q)
_NEAR_PAR = DatasetSpec("near", 10, 4000.0, CHAMELEON.bdp_mb * _Q)
NEAR_INTEGER = {
    "pp": (CHAMELEON, (_NEAR_PP,), CHAMELEON.bdp_mb / _NEAR_PP.avg_file_mb),
    "par": (CHAMELEON, (_NEAR_PAR,),
            _NEAR_PAR.avg_file_mb / CHAMELEON.bdp_mb),
    "cc": (_EDGE, (MEDIUM_FILES,),
           _EDGE.bandwidth_mbps / (_EDGE.avg_window_mb / _EDGE.rtt_s)),
}
CASES = {f"{tb}-{ds[0].name if len(ds) == 1 else 'mixed'}": (prof, ds)
         for tb, prof in TESTBEDS.items()
         for ds in ((SMALL_FILES,), (MEDIUM_FILES,), (LARGE_FILES,), MIXED)}
CASES.update({f"near-integer-{field}": (prof, ds)
              for field, (prof, ds, _) in NEAR_INTEGER.items()})


@pytest.mark.parametrize("case", sorted(CASES))
def test_initialize_rounds_as_float32_jnp(case):
    profile, specs = CASES[case]
    for sla in SLAS:
        params, chunked = heuristics.initialize(specs, profile, CPU, sla)
        (pp, par, cc, cores, freq_idx), want_chunked = _jnp_initialize(
            specs, profile, CPU, sla)
        for got, want in ((params.pp, pp), (params.par, par),
                          (params.cc, cc)):
            assert isinstance(got, np.ndarray) and got.dtype == np.float32
            np.testing.assert_array_equal(got, want)
        for got, want in ((params.cores, cores),
                          (params.freq_idx, freq_idx)):
            assert isinstance(got, np.ndarray) and got.dtype == np.int32
            assert got.shape == () and int(got) == want
        assert chunked == want_chunked
        for s in specs:
            assert (heuristics.split_large_files(s, profile.bdp_mb)
                    == _jnp_split_large_files(s, profile.bdp_mb))
        if case.startswith("near-integer-"):
            field = case.removeprefix("near-integer-")
            assert math.ceil(NEAR_INTEGER[field][2]) == 4
            assert getattr(params, field)[0] == 3.0


def _learned():
    cfg = learn.PolicyConfig(hidden=(4,))
    return api.make_controller("learned",
                               params=learn.init_policy(
                                   cfg, jax.random.PRNGKey(0)))


CONTROLLERS = {
    "ME": lambda: api.make_controller("me"),
    "EEMT": lambda: api.make_controller("eemt"),
    "EETT": lambda: api.make_controller("eett", target_tput_mbps=500.0),
    "EEMT-noscale": lambda: api.make_controller("eemt", scaling=False),
    "ismail-target": lambda: api.make_controller("ismail-target"),
    "wget/curl": lambda: api.make_controller("wget/curl"),
    "http/2": lambda: api.make_controller("http/2"),
    "ismail-min-energy": lambda: api.make_controller("ismail-min-energy"),
    "ismail-max-tput": lambda: api.make_controller("ismail-max-tput"),
    "learned": _learned,
}
DATASETS = {"multi-partition": (SMALL_FILES, MEDIUM_FILES),
            "files-over-bdp": (LARGE_FILES,)}


@pytest.mark.parametrize("datasets", sorted(DATASETS))
@pytest.mark.parametrize("controller", sorted(CONTROLLERS))
def test_prepare_touches_no_device(controller, datasets):
    sc = api.Scenario(profile=CHAMELEON, datasets=DATASETS[datasets],
                      controller=CONTROLLERS[controller](), total_s=30.0)
    # Stricter than "disallow": explicit device puts are refused too.
    with jax.transfer_guard("disallow_explicit"):
        prep = _prepare(sc)
    for leaf in jax.tree.leaves(prep.inputs):
        assert isinstance(leaf, np.ndarray)
