"""Plain reference of the transfer semantics the benchmark checks.

Written from the paper (arXiv 1904.05867: Algorithm 1 initialisation, the
Slow Start / ME / EEMT tuners, Algorithm 3 load control, the static
baselines of its Section V) and the simulator's documented tick model, in
``jax.numpy`` over a batch of lanes on the host CPU.  It imports nothing of
the program and takes nothing the program made: configurations arrive as
the JSON dicts of ``bench/configs``.

Every float is carried in ``dtype``: float64 for the reference (run under
``jax.enable_x64``), bfloat16 for the control, which is the reference one
precision below the configuration's float32.  Lanes are independent
transfers; partitions are padded with zero-byte columns, which are born
drained and take no channels.

``grid`` runs a batch of transfers, each under its own bandwidth schedule,
until each drains or exhausts its horizon, on the host CPU unless given
another ``device``.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

SLOW_START, INCREASE, WARNING, RECOVERY = 0, 1, 2, 3
STATIC, ME, EEMT = 0, 1, 2          # lane policies
TUNERS = {"ME": ME, "EEMT": EEMT}
STATIC_TOOLS = ("wget/curl", "http/2", "ismail-min-energy", "ismail-max-tput")
PATH_KEYS = ("bandwidth_mbps", "rtt_s", "avg_window_mb", "buffer_mb",
             "loss_knee", "cross_traffic")
CHUNK = 1024                        # grid ticks between early-exit checks


# ------------------------------------------------------------- set-up --

def _ceil(x: float) -> int:
    """Ceiling of a real that binary floats may carry a hair above an
    integer (1250 / (2.5 / 0.032) is 16, not 16.000000000000004)."""
    return math.ceil(x - 1e-9 * max(1.0, abs(x)))


def _floor(x: float) -> int:
    return math.floor(x + 1e-9 * max(1.0, abs(x)))


def _split_large(spec: dict, bdp: float) -> tuple[dict, float]:
    """Algorithm 1 lines 2-5: files larger than the BDP become BDP-sized
    chunks, i.e. a parallelism of ceil(avg / BDP)."""
    if spec["avg_file_mb"] > bdp > 0:
        par = float(_ceil(spec["avg_file_mb"] / bdp))
        return dict(spec, avg_file_mb=spec["avg_file_mb"] / par), par
    return dict(spec), 1.0


def init_transfer(tool: str, datasets: list, path: dict, cpu: dict,
                  tuner: dict, dt: float) -> dict:
    """Initial parameters and controller state of one transfer."""
    bdp = path["bandwidth_mbps"] * path["rtt_s"]
    top_f = len(cpu["freq_levels_ghz"]) - 1
    if tool in TUNERS:
        chunks = [_split_large(s, bdp) for s in datasets]
        specs = [c[0] for c in chunks]
        par = [c[1] for c in chunks]
        pp = [min(max(1.0, float(_ceil(bdp / max(s["avg_file_mb"], 1e-6)))),
                  128.0) for s in specs]
        per_ch = path["avg_window_mb"] / path["rtt_s"]
        n_ch = float(_ceil(path["bandwidth_mbps"] / max(per_ch, 1e-6)))
        sizes = [s["total_mb"] for s in specs]
        cc = [max(float(_ceil(z / max(sum(sizes), 1e-6) * n_ch)), 1.0)
              for z in sizes]
        policy = TUNERS[tool]
        cores, freq = (1, 0) if policy == ME else (cpu["num_cores"], 0)
    elif tool in STATIC_TOOLS:
        specs = [dict(s) for s in datasets]
        n = len(specs)
        if tool in ("wget/curl", "http/2"):
            pp = [1.0 if tool == "wget/curl" else 64.0] * n
            par, cc = [1.0] * n, [1.0] * n
        else:   # Ismail et al.: buffer sized to the BDP, no chunking
            par = [max(1.0, float(_floor(s["avg_file_mb"] / bdp)))
                   for s in specs]
            pp = [max(1.0, min(float(_ceil(
                bdp / max(s["avg_file_mb"], 1e-6))), 32.0)) for s in specs]
            cc = [max(1.0, min(float(s["num_files"]), 4.0)) for s in specs]
            if tool == "ismail-min-energy":
                cc = [max(1.0, c / 2.0) for c in cc]
        policy, cores, freq = STATIC, cpu["num_cores"], top_f
    else:
        raise ValueError(f"the reference has no tool {tool!r}")
    return {"policy": policy, "pp": pp, "par": par,
            "total_mb": [s["total_mb"] for s in specs],
            "avg_file_mb": [s["avg_file_mb"] for s in specs],
            "num_ch": float(sum(cc)), "cores": cores, "freq": freq,
            # Ticks between controller intervals ("Timeout").
            "ctrl_every": (max(int(round(tuner["timeout_s"] / dt)), 1)
                           if policy != STATIC else 1)}


def lane_arrays(inits: list, paths: list, n_p: int) -> tuple[dict, dict]:
    """(state, constants) of a batch of lanes as float64 / int32 numpy
    arrays; the caller casts floats to the working dtype."""
    def parts(key):
        return np.array([i[key] + [0.0] * (n_p - len(i[key]))
                         for i in inits], np.float64).reshape(-1, n_p)

    n = len(inits)
    state = {"remaining": parts("total_mb"),
             "window": np.full((n, n_p), 64.0 / 1024.0),
             **{k: np.zeros(n) for k in ("energy", "moved", "ref", "acc_mb",
                                         "acc_j", "acc_s")},
             "num_ch": np.array([i["num_ch"] for i in inits], np.float64),
             "fsm": np.full(n, SLOW_START, np.int32),
             "cores": np.array([i["cores"] for i in inits], np.int32),
             "freq": np.array([i["freq"] for i in inits], np.int32)}
    consts = {"pp": parts("pp"), "par": parts("par"),
              "avg_file": parts("avg_file_mb"),
              "policy": np.array([i["policy"] for i in inits], np.int32),
              "ctrl_every": np.array([i["ctrl_every"] for i in inits],
                                     np.int32)}
    for key in PATH_KEYS:
        consts[key] = np.array([p[key] for p in paths], np.float64)
    return state, consts


def _layout(tree: dict) -> tuple:
    """Static description of a dict of [L] / [L, P] arrays: (key, width,
    is_float, ndim) per entry, in order."""
    return tuple((k, 1 if v.ndim == 1 else v.shape[1],
                  bool(np.issubdtype(v.dtype, np.floating)), v.ndim)
                 for k, v in tree.items())


def _pack(tree: dict, dtype) -> tuple[np.ndarray, np.ndarray]:
    """A dict of lane arrays as one float matrix in ``dtype`` and one int32
    matrix (two host-to-device copies instead of one per entry)."""
    def cols(floats):
        return [v.reshape(len(v), -1) for v in tree.values()
                if np.issubdtype(v.dtype, np.floating) == floats]
    return (np.concatenate(cols(True), axis=1).astype(dtype),
            np.concatenate(cols(False), axis=1).astype(np.int32))


def _unpack(layout: tuple, f, i) -> dict:
    out, at = {}, {True: 0, False: 0}
    for key, width, is_float, ndim in layout:
        src, o = (f if is_float else i), at[is_float]
        col = src[:, o:o + width]
        out[key] = col if ndim == 2 else col[:, 0]
        at[is_float] = o + width
    return out


def _repack(layout: tuple, s: dict):
    """Inverse of :func:`_unpack` (inside a trace)."""
    def cols(floats):
        return [s[key].reshape(s[key].shape[0], -1)
                for key, _, is_float, _ in layout if is_float == floats]
    return (jnp.concatenate(cols(True), axis=1),
            jnp.concatenate(cols(False), axis=1))


# --------------------------------------------------------------- tick --

def _tick(s: dict, c: dict, step_idx, bw_scale, live, *, dt, cpu, tuner):
    """Advance every lane by one tick; lanes with ``live`` False keep their
    whole state."""
    d = s["remaining"].dtype
    rtt = c["rtt_s"][:, None]
    rem = s["remaining"]
    active = (rem > 0).astype(d)
    # Channels follow the remaining bytes of each partition.
    pos = jnp.maximum(rem, 0)
    w = pos / jnp.maximum(pos.sum(axis=1, keepdims=True), 1e-6)
    cc = jnp.maximum(w * s["num_ch"][:, None] * active, 0) * active
    total_ch = cc.sum(axis=1)
    n_active = jnp.maximum(active.sum(axis=1), 1)
    avg_win = (s["window"] * active).sum(axis=1) / n_active
    # One channel: parallel streams widen the window while chunks exceed
    # the socket buffer; each file costs rtt / pp of dead time.
    par_eff = jnp.clip(c["par"], 1, jnp.maximum(
        c["avg_file"] / c["buffer_mb"][:, None], 1))
    raw = par_eff * s["window"] / rtt
    per_file = (c["avg_file"] / jnp.maximum(raw, 1e-6)
                + rtt / jnp.maximum(c["pp"], 1))
    demand = cc * (c["avg_file"] / jnp.maximum(per_file, 1e-9))
    total_demand = demand.sum(axis=1)
    # Contention past the knee of saturation.
    per_ch = jnp.maximum(avg_win / c["rtt_s"], 1e-6)
    c_sat = c["loss_knee"] * c["bandwidth_mbps"] / per_ch
    over = jnp.maximum(total_ch - c_sat, 0) / jnp.maximum(c_sat, 1)
    eff = 1 / (1 + 0.5 * over * over)
    net_cap = (c["bandwidth_mbps"] * (1 - c["cross_traffic"])
               * bw_scale.astype(d) * eff)
    freqs = jnp.asarray(cpu["freq_levels_ghz"], d)
    cores = jnp.clip(s["cores"], 1, cpu["num_cores"]).astype(d)
    f = freqs[jnp.clip(s["freq"], 0, len(cpu["freq_levels_ghz"]) - 1)]
    cpb = cpu["cycles_per_byte"] + cpu["cycles_per_byte_per_ch"] * total_ch
    cpu_cap = cores * f * 1e9 * cpu["ipc"] / (cpb * 1e6)
    tput = jnp.minimum(jnp.minimum(total_demand, net_cap), cpu_cap)
    rate = demand * (tput / jnp.maximum(total_demand, 1e-6))[:, None]
    moved = jnp.minimum(rate * dt, rem)
    load = jnp.clip(tput / jnp.maximum(cpu_cap, 1e-6), 0, 1)
    power = (cpu["pkg_static_w"] + cores * cpu["core_static_w"]
             + cores * cpu["core_dyn_w_per_ghz3"] * f ** 3 * load
             + cpu["mem_w_per_mbps"] * tput)
    ramp = jnp.clip(dt / (8 * rtt), 0, 1)

    lv = live[:, None]
    s = dict(s)
    s["remaining"] = jnp.where(lv, rem - moved, rem)
    s["window"] = jnp.where(lv, s["window"] + (
        c["avg_window_mb"][:, None] - s["window"]) * ramp, s["window"])
    for key, inc in (("energy", power * dt), ("moved", moved.sum(axis=1)),
                     ("acc_mb", tput * dt), ("acc_j", power * dt),
                     ("acc_s", jnp.full_like(tput, dt))):
        s[key] = jnp.where(live, s[key] + inc, s[key])

    ctrl = (live & (c["policy"] != STATIC)
            & (step_idx % c["ctrl_every"] == c["ctrl_every"] - 1))
    return _control(s, c, ctrl, load, cpu=cpu, tuner=tuner)


def _control(s: dict, c: dict, ctrl, load, *, cpu, tuner) -> dict:
    """One controller interval (paper Fig. 1 FSM) for lanes ``ctrl``."""
    d = s["remaining"].dtype
    a, b = tuner["alpha"], tuner["beta"]
    dch, mx = float(tuner["delta_ch"]), float(tuner["max_ch"])
    tput = s["acc_mb"] / jnp.maximum(s["acc_s"], 1e-6)
    power = s["acc_j"] / jnp.maximum(s["acc_s"], 1e-6)
    left = s["remaining"].sum(axis=1)
    # ME's metric: energy of the last interval plus the energy the rest of
    # the transfer would take at the current rate and power.
    e_metric = s["acc_j"] + power * (left / jnp.maximum(tput, 1e-3))
    is_me = c["policy"] == ME
    fsm, ch, ref = s["fsm"], s["num_ch"], s["ref"]

    # Slow Start (Algorithm 2): one correction towards the path rate.
    corr = jnp.clip(c["bandwidth_mbps"] / jnp.maximum(tput, 1e-3), 0.25, 8.0)
    ss_ch = jnp.clip(ch * corr, 1, mx)
    ss_ref = jnp.where(is_me, e_metric, tput)

    # ME (Algorithm 4): lower metric is better.  EEMT (Algorithm 5): higher
    # throughput is better, the reference ratchets up.
    good = jnp.where(is_me, e_metric < (1 - a) * ref, tput > (1 + b) * ref)
    bad = jnp.where(is_me, e_metric > (1 + b) * ref, tput < (1 - a) * ref)
    metric = jnp.where(is_me, e_metric, tput)
    inc, warn = fsm == INCREASE, fsm == WARNING
    ch_inc = jnp.where(good, jnp.minimum(ch + dch, mx), ch)
    ref_inc = jnp.where(is_me, metric, jnp.where(good, tput, ref))
    fsm_inc = jnp.where(bad, WARNING, INCREASE)
    ch_warn = jnp.where(bad, jnp.maximum(ch - dch, 1), ch)
    fsm_warn = jnp.where(bad, RECOVERY, INCREASE)
    ch_rec = jnp.where(bad, jnp.minimum(ch + dch, mx), ch)
    ref_rec = jnp.where(bad, metric, ref)
    t_ch = jnp.where(inc, ch_inc, jnp.where(warn, ch_warn, ch_rec))
    t_fsm = jnp.where(inc, fsm_inc, jnp.where(warn, fsm_warn, INCREASE))
    t_ref = jnp.where(inc, ref_inc, jnp.where(warn, ref, ref_rec))

    in_ss = fsm == SLOW_START
    new_ch = jnp.where(in_ss, ss_ch, t_ch)
    new_fsm = jnp.where(in_ss, INCREASE, t_fsm)
    new_ref = jnp.where(in_ss, ss_ref, t_ref)

    # Algorithm 3: add cores before raising the frequency; lower the
    # frequency before parking cores.
    cores, freq = s["cores"], s["freq"]
    top = len(cpu["freq_levels_ghz"]) - 1
    hot, cold = load > tuner["max_load"], load < tuner["min_load"]
    more = cores < cpu["num_cores"]
    cores_hot = jnp.where(more, cores + 1, cores)
    freq_hot = jnp.where(more, freq, jnp.minimum(freq + 1, top))
    freq_cold = jnp.maximum(freq - 1, 0)
    cores_cold = jnp.where(freq > 0, cores, jnp.maximum(cores - 1, 1))
    new_cores = jnp.where(hot, cores_hot, jnp.where(cold, cores_cold, cores))
    new_freq = jnp.where(hot, freq_hot, jnp.where(cold, freq_cold, freq))

    z = jnp.zeros((), d)
    s["num_ch"] = jnp.where(ctrl, new_ch, ch).astype(d)
    s["fsm"] = jnp.where(ctrl, new_fsm, fsm).astype(jnp.int32)
    s["ref"] = jnp.where(ctrl, new_ref, ref).astype(d)
    s["cores"] = jnp.where(ctrl, new_cores, cores).astype(jnp.int32)
    s["freq"] = jnp.where(ctrl, new_freq, freq).astype(jnp.int32)
    for key in ("acc_mb", "acc_j", "acc_s"):
        s[key] = jnp.where(ctrl, z, s[key])
    return s


def _static(cfg: dict) -> tuple:
    """Hashable (dt, cpu, tuner) for the jitted runners."""
    def freeze(d):
        return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                            for k, v in d.items()))
    return cfg["dt"], freeze(cfg["cpu"]), freeze(cfg["tuner"])


def _thaw(static: tuple) -> tuple:
    dt, cpu, tuner = static
    return dt, dict(cpu), dict(tuner)


@contextlib.contextmanager
def _placed(dtype, device=None):
    """Run on ``device`` (the host CPU by default), with 64-bit types where
    the dtype needs them."""
    wide = jnp.dtype(dtype).itemsize == 8
    with jax.enable_x64(wide), jax.default_device(
            device or jax.devices("cpu")[0]):
        yield


# --------------------------------------------------------------- grid --

@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _grid_run(static, s_layout, c_layout, packed, bw, n_steps):
    """Chunks of ``CHUNK`` ticks until every lane is drained or past its
    horizon; returns the final state and each lane's drain tick (-1)."""
    state = _unpack(s_layout, *packed[:2])
    consts = _unpack(c_layout, *packed[2:])
    dt, cpu, tuner = _thaw(static)
    horizon = bw.shape[1]

    def chunk(carry):
        k, s, done_at = carry

        def step(carry, j):
            s, done_at = carry
            i = k * CHUNK + j
            live = (s["remaining"].sum(axis=1) > 0) & (i < n_steps)
            scale = jax.lax.dynamic_index_in_dim(
                bw, jnp.minimum(i, horizon - 1), axis=1, keepdims=False)
            s = _tick(s, consts, i, scale, live, dt=dt, cpu=cpu,
                      tuner=tuner)
            drained = live & (s["remaining"].sum(axis=1) <= 0)
            return (s, jnp.where(drained, i, done_at).astype(jnp.int32)), None

        (s, done_at), _ = jax.lax.scan(step, (s, done_at),
                                       jnp.arange(CHUNK, dtype=jnp.int32))
        return k + 1, s, done_at

    def more(carry):
        k, s, _ = carry
        return jnp.any((s["remaining"].sum(axis=1) > 0)
                       & (k * CHUNK < n_steps))

    done0 = jnp.full(n_steps.shape, -1, jnp.int32)
    _, s, done_at = jax.lax.while_loop(
        more, chunk, (jnp.zeros((), jnp.int32), state, done0))
    return s, done_at


def grid(cells: list, cfg: dict, dtype=np.float64,
         device=None) -> list[dict]:
    """Run each cell to completion or to its horizon.

    ``cells``: dicts with ``tool``, ``datasets``, ``path``, ``horizon_s``
    and ``bw`` (the per-tick share of the path rate, one float32 entry per
    tick of the horizon).  Returns per cell ``completed``, ``time_s``
    (drain tick + 1 ticks, or the horizon), ``energy_j`` and ``moved_mb``.
    """
    dt = cfg["dt"]
    inits = [init_transfer(c["tool"], c["datasets"], c["path"], cfg["cpu"],
                           cfg["tuner"], dt) for c in cells]
    n_p = max(len(i["total_mb"]) for i in inits)
    state, consts = lane_arrays(inits, [c["path"] for c in cells], n_p)
    n_steps = np.array([int(round(c["horizon_s"] / dt)) for c in cells],
                       np.int32)
    width = int(n_steps.max())
    bw = np.stack([np.pad(np.asarray(c["bw"], np.float32),
                          (0, width - len(c["bw"])), mode="edge")
                   for c in cells])
    with _placed(dtype, device):
        s, done_at = _grid_run(_static(cfg), _layout(state), _layout(consts),
                               (*_pack(state, dtype), *_pack(consts, dtype)),
                               bw, n_steps)
        done_at = np.asarray(done_at)
        energy = np.asarray(s["energy"].astype(jnp.float32), np.float64)
        moved = np.asarray(s["moved"].astype(jnp.float32), np.float64)
    out = []
    for i, c in enumerate(cells):
        completed = bool(done_at[i] >= 0)
        out.append({"completed": completed,
                    "time_s": dt * (int(done_at[i]) + 1) if completed
                    else c["horizon_s"],
                    "energy_j": float(energy[i]),
                    "moved_mb": float(moved[i])})
    return out
