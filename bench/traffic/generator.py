"""Traffic generator: pinned, integer-time PipeSim workloads from a seed.

A numpy copy of the draws of the program's synthesizer
(``sample_clustered_arrivals`` and ``_draw_tasks`` of
``repro.core.synthesizer``), reading the benchmark's own copy of the fitted
parameters (``pipesim_params.npz`` beside this file). It imports nothing of
the program and compiles nothing.

What a seed changes. Every seed gives the same set of pipelines (structures,
durations, sizes) and the same arrival times; the seed only decides which
pipeline arrives at which of those times, among neighbours in arrival
order. The set itself is drawn once per arrival rate from the mix's fixed
``base_seed``. So the work of a sweep (its pipeline count, its task-seconds,
its tensor shapes) is the same for every seed, and runs on different seeds
differ only in the order of nearby arrivals.

The integer-time form (floored arrivals; service time, execution plus data
transfer, ceiled into the execution time with the transfer bytes zeroed) is
exact in float32 below 2**24 s, so the program's float32 engine and the
float64 reference must agree bit for bit on it.
"""
from __future__ import annotations

import os

import numpy as np

PARAMS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "pipesim_params.npz")
# a seed permutes arrival times among the pipelines of each block of this
# many consecutive arrivals
REORDER_BLOCK = 64

# task types, frameworks and the slot layout of a pipeline (core/model.py,
# core/workload.py)
PREPROCESS, TRAIN, EVALUATE, COMPRESS, HARDEN, DEPLOY = range(6)
N_TASK_TYPES = 6
N_FRAMEWORKS = 5
MAX_TASKS = 6
TASK_ORDER = (PREPROCESS, TRAIN, EVALUATE, COMPRESS, HARDEN, DEPLOY)

# distribution families (core/stats.py)
LOGNORMAL, EXPONWEIB, PARETO, NORMAL, EXPONENTIAL = range(5)

def load_params(path: str = PARAMS_PATH) -> dict:
    """The fitted parameters as plain numpy arrays: a Dist is a
    ``(family, p0, p1, p2)`` tuple, a GMM a ``(log_weights, means, chol)``
    tuple."""
    z = np.load(path)

    def dist(prefix):
        return tuple(np.asarray(z[f"{prefix}.{i}"]) for i in range(4))

    def gmm(prefix):
        return tuple(np.asarray(z[f"{prefix}.{i}"], np.float64)
                     for i in range(3))

    return dict(
        asset_gmm=gmm("asset_gmm"),
        asset_lo=np.asarray(z["asset_lo"], np.float64),
        asset_hi=np.asarray(z["asset_hi"], np.float64),
        preproc_abc=np.asarray(z["preproc_abc"], np.float64),
        preproc_noise=dist("preproc_noise"),
        train_gmm=[gmm(f"train_gmm_{f}") for f in range(N_FRAMEWORKS)],
        eval_gmm=gmm("eval_gmm"),
        compress_noise=dist("compress_noise"),
        harden_ratio=dist("harden_ratio"),
        deploy=dist("deploy"),
        framework_mix=np.asarray(z["framework_mix"], np.float64),
        structure_probs=np.asarray(z["structure_probs"], np.float64),
        ia_global=dist("ia_global"),
        ia_clusters=dist("ia_clusters"),
        perf_gmm=[gmm(f"perf_gmm_{f}") for f in range(N_FRAMEWORKS)],
        msize_mu=np.asarray(z["msize_mu"], np.float64),
        msize_sd=np.asarray(z["msize_sd"], np.float64),
    )


# ------------------------------------------------------------ distributions

def dist_transform(family, p0, p1, p2, u, z):
    """Inverse-CDF / reparameterised transform of ``core/stats.py``."""
    with np.errstate(over="ignore", invalid="ignore", divide="ignore"):
        ln = np.exp(p0 + p1 * z)
        a = np.maximum(p0, 1e-6)
        c = np.maximum(p1, 1e-6)
        scale = np.maximum(p2, 1e-30)
        inner = -np.log1p(-np.power(u, 1.0 / a))
        ew = scale * np.power(np.maximum(inner, 1e-30), 1.0 / c)
        par = p1 + np.maximum(p2, 1e-30) * np.power(
            1.0 - u, -1.0 / np.maximum(p0, 1e-6))
        nrm = p0 + p1 * z
        expo = -np.maximum(p0, 1e-30) * np.log1p(-u)
    out = np.where(family == LOGNORMAL, ln, 0.0)
    out = np.where(family == EXPONWEIB, ew, out)
    out = np.where(family == PARETO, par, out)
    out = np.where(family == NORMAL, nrm, out)
    return np.where(family == EXPONENTIAL, expo, out)


def sample_dist(rng: np.random.Generator, d, n: int) -> np.ndarray:
    u = rng.uniform(1e-7, 1.0 - 1e-7, n)
    z = rng.standard_normal(n)
    fam, p0, p1, p2 = (np.asarray(x, np.float64) for x in d)
    return dist_transform(fam, p0, p1, p2, u, z)


def sample_gmm(rng: np.random.Generator, g, n: int) -> np.ndarray:
    log_w, means, chol = g
    w = np.exp(log_w - log_w.max())
    comp = rng.choice(len(w), size=n, p=w / w.sum())
    z = rng.standard_normal((n, means.shape[1]))
    return means[comp] + np.einsum("nij,nj->ni", chol[comp], z)


def sample_log_gmm_rejecting(rng, g, n: int, lo, hi,
                             oversample: int = 4) -> np.ndarray:
    """Draw ``oversample * n`` from the log-space GMM, keep the first ``n``
    in bounds, clip any shortfall (``core/gmm.py``)."""
    val = np.exp(sample_gmm(rng, g, oversample * n))
    ok = np.all((val >= lo[None]) & (val <= hi[None]), axis=-1)
    picked = val[np.argsort(~ok, kind="stable")[:n]]
    return np.clip(picked, lo[None], hi[None])


def clustered_arrivals(rng, params: dict, horizon_s: float,
                       interarrival_factor: float) -> np.ndarray:
    """Arrival times below ``horizon_s``; each gap is drawn from the cluster
    of the hour of week of the previous arrival."""
    mean_ia = max(float(np.mean(sample_dist(rng, params["ia_global"], 4096)))
                  * interarrival_factor, 1e-2)
    n_max = int(horizon_s / mean_ia * 1.6) + 64
    fam, p0, p1, p2 = (np.asarray(x, np.float64)
                       for x in params["ia_clusters"])
    u = rng.uniform(1e-7, 1.0 - 1e-7, n_max)
    z = rng.standard_normal(n_max)
    times = np.empty(n_max)
    t = 0.0
    for lo in range(0, n_max, 4096):
        # every cluster's gap for a block of draws, then the sequential pick
        hi = min(lo + 4096, n_max)
        cand = np.clip(dist_transform(fam[None, :], p0[None, :], p1[None, :],
                                      p2[None, :], u[lo:hi, None],
                                      z[lo:hi, None]),
                       1e-3, 24 * 3600.0) * interarrival_factor
        for i in range(hi - lo):
            t += cand[i, int(t // 3600.0) % 168]
            if t >= horizon_s:
                return times[:lo + i]
            times[lo + i] = t
    raise ValueError("arrival draw ran out before the horizon")


# ---------------------------------------------------------------- pipelines

def draw_pipelines(rng, params: dict, n: int) -> dict:
    """Structures, frameworks, assets and durations of ``n`` pipelines
    (``_draw_tasks``): ``task_type [n, 6]`` (-1 padded), ``n_tasks``,
    ``exec_time``, ``read_bytes``, ``write_bytes`` ``[n, 6]``,
    ``framework``, ``model_perf``, ``model_size``, ``model_clever``."""
    present = rng.uniform(size=(n, N_TASK_TYPES)) \
        < params["structure_probs"][None, :]
    present[:, TRAIN] = True
    present[:, DEPLOY] &= present[:, EVALUATE]
    tt = np.full((n, MAX_TASKS), -1, np.int32)
    cnt = np.zeros(n, np.int32)
    for ttype in TASK_ORDER:
        m = present[:, ttype]
        tt[m, cnt[m]] = ttype
        cnt[m] += 1

    mix = params["framework_mix"] + 1e-12
    fw = rng.choice(N_FRAMEWORKS, size=n, p=mix / mix.sum()).astype(np.int32)

    assets = sample_log_gmm_rejecting(rng, params["asset_gmm"], n,
                                      params["asset_lo"], params["asset_hi"])
    rows, cols, nbytes = assets[:, 0], assets[:, 1], assets[:, 2]

    a, b, c = params["preproc_abc"]
    x = np.log(np.maximum(rows * cols, 1.0))
    t_pre = (a * np.power(b, np.clip(x, 0.0, 26.0)) + c) \
        * sample_dist(rng, params["preproc_noise"], n)
    t_train = np.zeros(n)
    for f in range(N_FRAMEWORKS):
        m = fw == f
        if m.any():
            t_train[m] = np.exp(sample_gmm(rng, params["train_gmm"][f],
                                           int(m.sum()))[:, 0])
    t_eval = np.exp(sample_gmm(rng, params["eval_gmm"], n)[:, 0])
    t_comp = t_train * np.clip(sample_dist(rng, params["compress_noise"], n),
                               0.05, 10.0)
    t_hard = t_train * np.clip(sample_dist(rng, params["harden_ratio"], n),
                               0.05, 50.0)
    t_depl = sample_dist(rng, params["deploy"], n)

    perf = np.zeros(n, np.float32)
    for f in range(N_FRAMEWORKS):
        m = fw == f
        if m.any():
            s = sample_gmm(rng, params["perf_gmm"][f], int(m.sum()))[:, 0]
            perf[m] = 1.0 / (1.0 + np.exp(-s))
    msize = np.exp(params["msize_mu"][fw]
                   + params["msize_sd"][fw] * rng.standard_normal(n))
    clever = np.exp(rng.standard_normal(n) * 0.5 + np.log(0.3))

    per_type = {PREPROCESS: t_pre, TRAIN: t_train, EVALUATE: t_eval,
                COMPRESS: t_comp, HARDEN: t_hard, DEPLOY: t_depl}
    reads = {PREPROCESS: nbytes, TRAIN: nbytes, EVALUATE: msize + 0.2 * nbytes,
             COMPRESS: msize, HARDEN: msize + nbytes, DEPLOY: msize}
    writes = {PREPROCESS: nbytes, TRAIN: msize, EVALUATE: 0.0 * msize,
              COMPRESS: 0.4 * msize, HARDEN: msize, DEPLOY: 0.0 * msize}
    exec_time = np.zeros((n, MAX_TASKS))
    read_b = np.zeros((n, MAX_TASKS))
    write_b = np.zeros((n, MAX_TASKS))
    for j in range(MAX_TASKS):
        for ttype in TASK_ORDER:
            m = tt[:, j] == ttype
            exec_time[m, j] = np.maximum(per_type[ttype][m], 1e-2)
            read_b[m, j] = reads[ttype][m]
            write_b[m, j] = writes[ttype][m]
    return dict(task_type=tt, n_tasks=cnt, exec_time=exec_time,
                read_bytes=read_b, write_bytes=write_b, framework=fw,
                model_perf=perf, model_size=msize.astype(np.float32),
                model_clever=clever.astype(np.float32))


def service_time(p: dict, datastore: dict) -> np.ndarray:
    """Resource-holding time per task: execution plus a latency and a
    bandwidth term per read and per write (``Workload.service_time``)."""
    io = (p["read_bytes"] > 0) * (datastore["latency"] + p["read_bytes"]
                                  / datastore["read_bandwidth"])
    io = io + (p["write_bytes"] > 0) * (datastore["latency"]
                                        + p["write_bytes"]
                                        / datastore["write_bandwidth"])
    return p["exec_time"] + io


def integer_time(arrival: np.ndarray, p: dict, datastore: dict) -> tuple:
    """Floored arrivals; service ceiled into the execution time, bytes
    zeroed. Dead task slots keep a zero execution time."""
    live = p["task_type"] >= 0
    q = dict(p)
    q["exec_time"] = np.ceil(service_time(p, datastore)) * live
    q["read_bytes"] = np.zeros_like(p["read_bytes"])
    q["write_bytes"] = np.zeros_like(p["write_bytes"])
    return np.floor(arrival), q


def _rng(*words) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence(
        [int(w) % (1 << 64) for w in words]))


def make_workload(params: dict, *, horizon_s: float,
                  interarrival_factor: float, base_seed: int, seed: int,
                  routing: dict, datastore: dict, level: int = 0) -> dict:
    """One pinned workload as numpy columns (``arrival``, ``n_tasks``,
    ``task_type``, ``task_res``, ``exec_time``, ``read_bytes``,
    ``write_bytes``, ``framework``, ``priority``, ``model_perf``,
    ``model_size``, ``model_clever``).

    ``interarrival_factor`` scales every gap between arrivals, as the
    program's knob of that name does (0.5: twice the arrivals). ``routing``
    maps task type to resource index; ``datastore`` holds ``latency``,
    ``read_bandwidth`` and ``write_bandwidth``. The set of pipelines and
    the arrival times come from ``base_seed`` and ``level`` (the index of
    this arrival rate in the mix). ``seed`` permutes the arrival times
    among the pipelines within each block of :data:`REORDER_BLOCK`
    consecutive arrivals, so the load over the week stays where it is.
    Row ``i`` is always the same pipeline: only its arrival time moves, so
    every draw the program makes per row (failures, evictions) lands on
    the same pipeline for every seed, and rows are no longer in arrival
    order."""
    if horizon_s >= 2.0 ** 24:
        raise ValueError("integer-time horizons must stay below 2**24 s")
    base = _rng(base_seed, level, 0x7A)
    arrival = clustered_arrivals(base, params, horizon_s,
                                 interarrival_factor)
    n = arrival.shape[0]
    pipes = draw_pipelines(base, params, n)
    block = np.arange(n) // REORDER_BLOCK
    order = np.argsort(block + _rng(seed, level, 0x5E).random(n))
    arrival, pipes = integer_time(arrival[order], pipes, datastore)
    table = np.zeros(N_TASK_TYPES, np.int32)
    for t, r in routing.items():
        table[int(t)] = int(r)
    tt = pipes["task_type"]
    pipes["task_res"] = (table[np.maximum(tt, 0)] * (tt >= 0)).astype(np.int32)
    pipes["priority"] = np.zeros(n, np.float32)
    pipes["arrival"] = arrival
    return pipes
