"""Jit'd public wrapper for the aggregation kernel.

On the CPU backend (tests, ``JAX_PLATFORMS=cpu`` rehearsals) the kernel
runs in interpret mode — the kernel body executes per grid step,
validating the exact TPU program. Any other backend compiles it with
Mosaic. VMEM budgeting: the parameter tile bp is halved until one grid
step's accounted footprint (``aggregate.vmem_bytes``: the double-buffered
(N, bp) tile, one f32 copy of it, and the small rows) fits
``aggregate.VMEM_BUDGET``; the kernel passes that footprint on as its
``vmem_limit_bytes``.
"""

from __future__ import annotations

import jax

from repro.core.aggregation import compose_masks  # noqa: F401  (re-export:
# the mask operand of every kernel below accepts a composed product of
# active/delivery masks — canonical impl lives with the mask machinery)
from repro.kernels import interpret_default
from repro.kernels.aggregate.aggregate import (
    VMEM_BUDGET,
    masked_scaled_aggregate_kernel,
    masked_scaled_aggregate_update_kernel,
    vmem_bytes,
)


def _fit_block(n: int, itemsize: int, block_p: int) -> int:
    while block_p > 128 and vmem_bytes(n, block_p, itemsize) > VMEM_BUDGET:
        block_p //= 2
    return block_p


def masked_scaled_aggregate(g, w, block_p: int = 2048, out_dtype=None,
                            mask=None):
    """out[p] = Σ_n w[n]·g[n,p].  g: (N, P); w: (N,) -> (P,).

    ``out_dtype`` optionally overrides the output dtype (f32 in-kernel
    accumulation either way). ``mask`` is an optional (N,) 0/1
    active-row operand: masked rows are zero-selected inside the tile
    (exact-zero contribution even for non-finite rows).
    """
    block_p = _fit_block(g.shape[0], g.dtype.itemsize, block_p)
    return masked_scaled_aggregate_kernel(
        g, w, mask, block_p=block_p, interpret=interpret_default(),
        out_dtype=out_dtype)


def masked_scaled_aggregate_update(g, w, eta, params=None, mask=None, *,
                                   block_p: int = 2048, out_dtype=None):
    """Fused reduce-and-update (DESIGN.md §9), one tiled launch:

    * ``params`` given: ``params − eta·(w_sel @ g)`` — the full flat SGD
      server step (output in ``params.dtype`` unless overridden).
    * ``params`` None: the local delta ``−eta·(w_sel @ g)`` in f32 (the
      client-sharded form; the caller psums the delta across shards).

    ``mask`` rows are zero-selected inside the tile (exact-zero
    contribution even for non-finite rows); in-kernel accumulation is
    f32 either way.
    """
    block_p = _fit_block(g.shape[0], g.dtype.itemsize, block_p)
    return masked_scaled_aggregate_update_kernel(
        g, w, eta, params, mask, block_p=block_p,
        interpret=interpret_default(), out_dtype=out_dtype)


def masked_scaled_aggregate_sharded(g, w, *, axis_name: str,
                                    block_p: int = 2048, out_dtype=None,
                                    mask=None):
    """Client-sharded operands (DESIGN.md §8): each device launches the
    tiled kernel over its local ``(n_local, P)`` gradient rows, then the
    ``(P,)`` partials psum across ``axis_name``. The in-kernel and
    cross-device accumulation both stay f32; the result is cast to
    ``out_dtype`` only after the collective, so low-precision outputs
    never round-trip through the reduction."""
    import jax.numpy as jnp

    partial = masked_scaled_aggregate(g, w, block_p=block_p,
                                      out_dtype=jnp.float32, mask=mask)
    out = jax.lax.psum(partial, axis_name)
    od = jnp.dtype(out_dtype) if out_dtype is not None else g.dtype
    return out.astype(od)
