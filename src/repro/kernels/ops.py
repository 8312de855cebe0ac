"""Jit'd public wrappers for the Pallas kernels.

Off-TPU the kernels execute in interpret mode — the kernel body runs as
ordinary XLA ops for correctness validation; on TPU they compile to Mosaic.
``interpret=None`` picks from the backend; pass ``interpret=True`` to force
the interpreter.
"""
from __future__ import annotations

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.gmm_logpdf import gmm_logpdf as _gmm
from repro.kernels.mamba2_scan import mamba2_scan as _mamba
from repro.kernels.queue_scan import _auto_interpret as _default_interpret
from repro.kernels.queue_scan import fused_admission  # noqa: F401  (re-export)
from repro.kernels.queue_scan import queue_scan as _queue


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _flash(q, k, v, causal=causal, block_q=block_q, block_k=block_k,
                  interpret=interpret)


def mamba2_scan(x, dt, A, Bm, Cm, *, chunk: int = 128, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _mamba(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)


def queue_scan(ready, service, *, capacity: int, interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _queue(ready, service, capacity=capacity, interpret=interpret)


def gmm_logpdf(x, means, inv_chol, log_w, *, block_n: int = 1024,
               interpret=None):
    interpret = _default_interpret() if interpret is None else interpret
    return _gmm(x, means, inv_chol, log_w, block_n=block_n,
                interpret=interpret)
