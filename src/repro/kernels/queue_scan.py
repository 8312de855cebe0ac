"""Admission/queue Pallas kernels — the DES hot loop (DESIGN.md §3).

Two kernels share this module:

``queue_scan`` — c-server FIFO queue scan. Given per-resource job streams
sorted by ready time, computes exact start / finish times of an M/G/c FIFO
station: the carry is each queue's c server-free times, held in VMEM; each
job takes the earliest-free server. Queues lie on the 128 lanes and jobs on
sublanes, so one program advances 128 stations (resource x replica) in
lockstep, and the job stream passes through VMEM in blocks with the
server-free times carried in scratch. The inner step is a min + masked
update over a (c, 128) tile — VPU work, not MXU. Oracle:
:func:`repro.core.des.single_station_fifo`.

``fused_admission`` — ONE ranked admission round of the wave loop
(``vdes._admission_stage``), fused: lexicographic rank over
``(resource, policy key, enqueue wave, pipeline id)``, capacity prefix
test, and slot assignment in a single ``pallas_call`` instead of the 3-key
``lax.sort`` + segment-scan + unsort-scatter round. The ranking is
computed as a pairwise *seat count* (VMEM-resident, one row block per
program): a job's seat under the stable lexicographic sort equals the
number of same-resource jobs with strictly lex-smaller keys — full keys
are unique because the pipeline id breaks every tie — so

    admitted_i  =  seat_i < free[res_i]

is bit-identical to the sorted-seat test (and to
:func:`repro.core.vdes.admission_mask_dense`, the same counting argument
executed as plain XLA ops). Selected via ``simulate(...,
admission_sort="pallas")``; parity with the ``"fused"`` / ``"chained"`` /
``"dense"`` paths is asserted by tests and gated by
``artifacts/BENCH_kernels.json: pallas_vs_lax_admission_drift``.

Both kernels run in interpret mode exactly when the backend is not a TPU
— kernel bodies then run through the Pallas interpreter as ordinary
traceable XLA ops, so they work under ``jit``/``vmap``/``lax.while_loop``
on the CPU. On a TPU they compile; only an explicit ``interpret=True``
argument, which no engine path passes, interprets there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# lane width: pad job axes to a multiple of this (f32 min tile is (8, 128))
_LANES = 128
# sublane count of an f32 tile
_SUBLANES = 8
# queue_scan job-block height: the job stream streams through VMEM in
# blocks of this many jobs, the server-free times carried in scratch
_JOB_BLOCK = 512


def _auto_interpret() -> bool:
    """Interpret kernels exactly when the backend is not a TPU — the
    canonical backend check, shared with :mod:`repro.kernels.ops`."""
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ queue_scan

def _queue_kernel(ready_ref, service_ref, start_ref, finish_ref, slots_ref,
                  *, capacity: int):
    """One (job block x 128 queues) tile: jobs on sublanes, one queue per
    lane. ``slots_ref [cap_pad, 128]`` holds each queue's server-free
    times across job blocks; padding slots are +inf and never the min."""
    cap_pad = slots_ref.shape[0]
    sid = jax.lax.broadcasted_iota(jnp.int32, (cap_pad, _LANES), 0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        slots_ref[...] = jnp.where(sid < capacity, 0.0,
                                   jnp.inf).astype(jnp.float32)

    def chunk(c, _):
        # one aligned (8, 128) tile of jobs per step; its rows in order
        base = pl.multiple_of(c * _SUBLANES, _SUBLANES)
        rdy = ready_ref[pl.ds(base, _SUBLANES), :]
        svc = service_ref[pl.ds(base, _SUBLANES), :]
        slots = slots_ref[...]
        starts, finishes = [], []
        for i in range(_SUBLANES):
            free_at = jnp.min(slots, axis=0, keepdims=True)      # [1, 128]
            k = jnp.min(jnp.where(slots == free_at, sid, cap_pad), axis=0,
                        keepdims=True)                           # first min
            s = jnp.maximum(rdy[i:i + 1, :], free_at)
            f = s + svc[i:i + 1, :]
            starts.append(s)
            finishes.append(f)
            slots = jnp.where(sid == k, f, slots)
        slots_ref[...] = slots
        start_ref[pl.ds(base, _SUBLANES), :] = jnp.concatenate(starts, 0)
        finish_ref[pl.ds(base, _SUBLANES), :] = jnp.concatenate(finishes, 0)
        return 0

    jax.lax.fori_loop(0, ready_ref.shape[0] // _SUBLANES, chunk, 0)


@functools.partial(jax.jit, static_argnames=("capacity", "interpret"))
def _queue_scan_call(ready, service, *, capacity: int, interpret: bool):
    R, N = ready.shape
    jb = min(_JOB_BLOCK, -(-N // _SUBLANES) * _SUBLANES)
    n_pad = -(-N // jb) * jb
    q_pad = -(-R // _LANES) * _LANES
    cap_pad = -(-capacity // _SUBLANES) * _SUBLANES

    # [R, N] -> [N, R]: queues on lanes. Padding jobs trail every real job
    # of their queue, so FIFO never lets them move a real start.
    def lay(x):
        return jnp.pad(x.astype(jnp.float32).T, ((0, n_pad - N),
                                                 (0, q_pad - R)))

    spec = pl.BlockSpec((jb, _LANES), lambda q, j: (j, q))
    start, finish = pl.pallas_call(
        functools.partial(_queue_kernel, capacity=capacity),
        grid=(q_pad // _LANES, n_pad // jb),
        in_specs=[spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct((n_pad, q_pad), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((cap_pad, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lay(ready), lay(service))
    return start[:N, :R].T, finish[:N, :R].T


def queue_scan(ready: jnp.ndarray, service: jnp.ndarray, *, capacity: int,
               interpret=None):
    """ready, service: [R, N] (sorted by ready within each row).
    Returns (start, finish): [R, N] f32 — exact M/G/c FIFO station times
    (oracle: :func:`repro.core.des.single_station_fifo` per row).
    ``interpret=None`` interprets exactly when the backend is not a TPU."""
    if interpret is None:
        interpret = _auto_interpret()
    return _queue_scan_call(ready, service, capacity=capacity,
                            interpret=bool(interpret))


# -------------------------------------------------------- fused_admission

def _admission_kernel(res_r_ref, pk_r_ref, wv_r_ref,
                      res_c_ref, pk_c_ref, wv_c_ref,
                      free_ref, out_ref, *, nres: int, blk: int, n_pad: int):
    """One row block of the pairwise seat count. ``*_r_ref`` are this
    program's ``[1, blk]`` row slices, ``*_c_ref`` the full ``[1, n_pad]``
    column views of the same arrays (VMEM-resident). Comparisons only — no
    float arithmetic — so the admitted mask is exact."""
    i = pl.program_id(0)
    ri = res_r_ref[...].reshape(blk, 1)              # rows as a column
    pi = pk_r_ref[...].reshape(blk, 1)
    wi = wv_r_ref[...].reshape(blk, 1)
    rj = res_c_ref[...]                              # [1, n_pad] -> cols
    pj = pk_c_ref[...]
    wj = wv_c_ref[...]
    # lexicographic key_j < key_i over (pkey, enq_wave, id); ids via 2D
    # iota (TPU requires >= 2D iota)
    col_id = jax.lax.broadcasted_iota(jnp.int32, (blk, n_pad), 1)
    row_id = i * blk + jax.lax.broadcasted_iota(jnp.int32, (blk, n_pad), 0)
    lt = (pj < pi) | ((pj == pi)
                      & ((wj < wi) | ((wj == wi) & (col_id < row_id))))
    seat = jnp.sum(((rj == ri) & lt).astype(jnp.int32), axis=1,
                   keepdims=True)                    # [blk, 1]
    # free[res] via a static unrolled select (nres is tiny); sentinel rows
    # (res == nres: not queued, or padding) keep 0 and never admit
    free_q = jnp.zeros((blk, 1), jnp.int32)
    for r in range(nres):
        free_q = jnp.where(ri == r, free_ref[0, r], free_q)
    adm = (ri < nres) & (seat < free_q)
    out_ref[...] = adm.reshape(1, blk).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("nres", "interpret"))
def _fused_admission_call(res_q, pkey, enq_wave, free, *, nres: int,
                          interpret: bool):
    n = res_q.shape[0]
    n_pad = max(_LANES, -(-n // _LANES) * _LANES)
    pad = n_pad - n
    # padding jobs carry the res == nres sentinel: they never admit and,
    # sharing no resource with real jobs, never change a real seat count
    res_p = jnp.pad(res_q.astype(jnp.int32), (0, pad),
                    constant_values=nres)[None, :]
    pk_p = jnp.pad(pkey.astype(jnp.float32), (0, pad))[None, :]
    wv_p = jnp.pad(enq_wave.astype(jnp.int32), (0, pad))[None, :]
    free_p = jnp.pad(free.astype(jnp.int32), (0, _LANES - nres))[None, :]
    blk = _LANES
    kernel = functools.partial(_admission_kernel, nres=nres, blk=blk,
                               n_pad=n_pad)
    row_spec = pl.BlockSpec((1, blk), lambda i: (0, i))
    col_spec = pl.BlockSpec((1, n_pad), lambda i: (0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(n_pad // blk,),
        in_specs=[row_spec, row_spec, row_spec,
                  col_spec, col_spec, col_spec,
                  pl.BlockSpec((1, _LANES), lambda i: (0, 0))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.int32),
        interpret=interpret,
    )(res_p, pk_p, wv_p, res_p, pk_p, wv_p, free_p)
    return out[0, :n] > 0


def fused_admission(res_q: jnp.ndarray, pkey: jnp.ndarray,
                    enq_wave: jnp.ndarray, free: jnp.ndarray,
                    *, interpret=None) -> jnp.ndarray:
    """The wave loop's fused admission round: ``[N]`` bool admitted mask.

    ``res_q [N]`` i32 — each job's resource, with the ``nres`` sentinel for
    non-queued rows; ``pkey [N]`` f32 — the policy key (0 FIFO, -priority,
    or service time for SJF); ``enq_wave [N]`` i32 — FIFO tie-break wave
    counter; ``free [nres]`` i32 — free slots per resource. Bit-identical
    to the ``lax.sort`` ranking in ``vdes._admission_stage`` (see module
    docstring for the seat-count argument). ``interpret=None`` interprets
    exactly when the backend is not a TPU."""
    if interpret is None:
        interpret = _auto_interpret()
    return _fused_admission_call(res_q, pkey, enq_wave, free,
                                 nres=int(free.shape[0]),
                                 interpret=bool(interpret))
