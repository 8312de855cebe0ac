"""Bit-parity numeric helpers shared by the numpy and JAX engines.

The engines' contract is f32 *op-for-op* equality: numpy rounds after every
operation, so any backend freedom to reassociate or contract breaks parity.
The one contraction XLA actually performs on this code is fusing a product
into an adjacent add/sub as a single FMA (``a - b*c`` keeps the infinitely
precise product; numpy rounds it first) — the PR 5 drift bug class. These
helpers make the rounding point explicit:

- :func:`rounded_product` — ``b*c`` rounded to its storage dtype *before*
  any consumer can fuse it. On numpy this is a plain multiply (numpy always
  rounds); on JAX the product is wrapped in ``lax.optimization_barrier`` so
  XLA cannot contract it into a downstream add/sub.
- :func:`fma_free_madd` / :func:`fma_free_msub` — ``a + b*c`` / ``a - b*c``
  with the product rounded first: the drop-in replacements the
  ``engine-fma`` / ``while-fma`` analyzer rules point at.
- :func:`guarded_denominator` — a denominator with padded/disabled rows
  mapped to 1 so a batched division can never mint NaN/inf values that the
  unbatched numpy mirror would not produce (the ``unguarded-div`` rule).

Everything takes the usual ``xp`` namespace argument (``numpy`` or
``jax.numpy``) so one call site serves both engines.
"""
from __future__ import annotations

import numpy as np


def rounded_product(b, c, xp=np):
    """``b * c`` rounded to the storage dtype before any downstream use.

    numpy rounds every op by construction. For JAX the product is passed
    through ``lax.optimization_barrier``, which pins it as a materialized
    value — XLA cannot contract it with a neighbouring add/sub into an FMA,
    so both engines see the identical (rounded) product.
    """
    prod = xp.multiply(b, c)
    if xp is np:
        return prod
    import jax

    return jax.lax.optimization_barrier(prod)


def fma_free_madd(a, b, c, xp=np):
    """``a + b*c`` with the product rounded first (never a fused FMA)."""
    return a + rounded_product(b, c, xp=xp)


def fma_free_msub(a, b, c, xp=np):
    """``a - b*c`` with the product rounded first (never a fused FMA)."""
    return a - rounded_product(b, c, xp=xp)


def guarded_denominator(den, enabled=None, xp=np):
    """A division-safe denominator: rows that must not divide map to 1.

    ``enabled`` masks the live rows (default ``den > 0``) — batched padding
    rows are all-zero by convention, and ``0/0`` or ``x/0`` would mint
    NaN/inf values the unbatched numpy mirror never computes. The masked
    rows' quotients are junk by construction; callers must select them away
    (they already do, via the same ``enabled`` mask).
    """
    if enabled is None:
        enabled = den > 0
    return xp.where(enabled, den, xp.ones_like(den))
