"""Pallas TPU kernel: masked/scaled client-gradient aggregation.

The server update (paper eq. 11/12) reduces N client gradients with
weights ω_i = p_i·mask_i·scale_i:

    out[p] = Σ_n ω[n] · g[n, p]

i.e. a (1,N)×(N,P) matvec — tall-skinny, memory-bound. The TPU-native
layout: tile the parameter axis into lane-aligned blocks resident in
VMEM; the client axis (N ≤ a few thousand) rides the sublane dimension in
full so each grid step is a single MXU matvec over an (N, bp) tile. The
weight vector is tiny and replicated to every grid step.

Grid: (P // bp,). VMEM per step (:func:`vmem_bytes`): Pallas
double-buffers every blocked operand, so the (N, bp) gradient tile counts
twice, plus one f32 copy of it for the body's row select / upcast, plus
the small (1, bp) and (1, N) rows. N=1024, bp=2048, f32 is thus ~24 MiB,
past v5e's 16 MiB default scoped-VMEM limit: ops.py halves bp until the
footprint fits :data:`VMEM_BUDGET`, and every call passes its footprint
as ``vmem_limit_bytes`` so very wide client axes (where even bp=128
exceeds the budget) still compile. FLOPs 2·N·P, bytes ≈ N·P·itemsize ⇒
arithmetic intensity ~2/itemsize: firmly memory-bound, so the win vs. a
naive XLA reduce chain is avoiding the (N,P)→(P,) reduction
materializing intermediates in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import CompilerParams

#: Scoped-VMEM footprint the block choice aims under: v5e's default
#: scoped limit, a quarter of its 128 MiB VMEM.
VMEM_BUDGET = 16 * 1024 * 1024
#: Headroom over the accounted footprint for Mosaic's own scratch.
_VMEM_SLACK = 4 * 1024 * 1024
#: Physical VMEM of one v5e TensorCore: no limit may ask for more.
VMEM_PHYSICAL = 128 * 1024 * 1024
#: Contraction precision of every kernel body. Mosaic's default rounds
#: f32 operands to bf16 on the MXU, as XLA's does; HIGHEST keeps the f32
#: contract (and matches ``reduce_flat``'s jnp matvec, which asks for the
#: same). The matvec stays memory-bound at the extra MXU passes.
_PRECISION = jax.lax.Precision.HIGHEST


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(n: int, bp: int, itemsize: int) -> int:
    """Accounted VMEM of one grid step over an (n, bp) gradient tile.

    Every blocked operand is double-buffered: the gradient tile (rows
    padded to the sublane packing, 8 for f32 and 16 for bf16), the
    (1, bp) parameter/output tiles and the (1, n) weight/mask rows (each
    padded to 8 f32 sublanes). The kernel bodies also hold one f32 copy
    of the tile (mask select, bf16 upcast).
    """
    tile = _round_up(n, 32 // itemsize) * bp * itemsize
    rows = 8 * 4 * (2 * bp + 2 * _round_up(n, 128))
    return 2 * (tile + rows) + _round_up(n, 8) * bp * 4


def _compiler_params(n: int, bp: int, itemsize: int) -> CompilerParams:
    limit = max(VMEM_BUDGET, vmem_bytes(n, bp, itemsize)) + _VMEM_SLACK
    if limit > VMEM_PHYSICAL:
        raise ValueError(
            f"aggregation kernel at N={n}, bp={bp}, itemsize={itemsize} "
            f"needs {limit} B of VMEM, more than the chip's "
            f"{VMEM_PHYSICAL} B; shard the client axis (DESIGN.md §8)")
    return CompilerParams(vmem_limit_bytes=limit)


def _agg_kernel(w_ref, g_ref, o_ref):
    # w: (1, N) f32; g: (N, bp); o: (1, bp)
    g = g_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.dot(w_ref[...], g, precision=_PRECISION,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _agg_kernel_masked(w_ref, m_ref, g_ref, o_ref):
    # w, m: (1, N) f32; g: (N, bp); o: (1, bp).  The mask is a row
    # *select*, not a multiplicand: masked rows are replaced by zeros
    # before the matvec, so a padded client contributes exactly 0 even
    # when its gradient row is inf/NaN garbage (0·inf would be NaN).
    g = g_ref[...].astype(jnp.float32)
    g = jnp.where(m_ref[...].T > 0, g, 0.0)
    o_ref[...] = jnp.dot(w_ref[...], g, precision=_PRECISION,
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _agg_update_kernel(eta_ref, w_ref, m_ref, g_ref, p_ref, o_ref):
    # eta: (1, 1) f32; w, m: (1, N) f32; g: (N, bp); p, o: (1, bp).
    # The fused server step (DESIGN.md §9): mask-select, weighted
    # reduction, and the SGD update in one tile visit — the gradient
    # block is read from HBM exactly once and no (P,)-sized aggregate
    # ever materializes outside VMEM. Accumulation is f32 (MXU
    # contract); the parameter tile is upcast, updated in f32, and cast
    # back only on the way out.
    g = g_ref[...].astype(jnp.float32)
    g = jnp.where(m_ref[...].T > 0, g, 0.0)
    acc = jnp.dot(w_ref[...], g, precision=_PRECISION,
                  preferred_element_type=jnp.float32)
    o_ref[...] = (p_ref[...].astype(jnp.float32)
                  - eta_ref[0, 0] * acc).astype(o_ref.dtype)


def _agg_delta_kernel(eta_ref, w_ref, m_ref, g_ref, o_ref):
    # Same fused tile minus the parameter operand: emits the local
    # update *delta* −eta·(w @ g_sel). The client-sharded step psums
    # this (P,)-sized delta across shards and adds it to the replicated
    # parameters — SGD is linear in the gradient, so the sum of local
    # deltas equals the delta of the global reduction.
    g = g_ref[...].astype(jnp.float32)
    g = jnp.where(m_ref[...].T > 0, g, 0.0)
    acc = jnp.dot(w_ref[...], g, precision=_PRECISION,
                  preferred_element_type=jnp.float32)
    o_ref[...] = (-eta_ref[0, 0] * acc).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_p", "interpret", "out_dtype"))
def masked_scaled_aggregate_kernel(g, w, mask=None, *, block_p: int = 2048,
                                   interpret: bool = False, out_dtype=None):
    """g: (N, P); w: (N,) -> (P,) = w @ g.

    P is padded to a multiple of ``block_p`` internally — one padding of
    the whole flat buffer, which is why the flat aggregation path
    (DESIGN.md §5) ravels the gradient pytree *before* calling in rather
    than launching per leaf. ``out_dtype`` overrides the output dtype
    (the in-kernel accumulation is f32 regardless), e.g. f32 server
    aggregates from bf16 client gradients. ``mask`` is an optional (N,)
    0/1 active-row operand (ragged populations, DESIGN.md §7): masked
    rows are zero-selected inside the tile before the MXU matvec, so
    they contribute exact zeros regardless of their contents; without a
    mask the two-operand program is unchanged.
    """
    n, p = g.shape
    bp = min(block_p, p)
    pad = (-p) % bp
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad)))
    pp = p + pad
    out_shape = jax.ShapeDtypeStruct(
        (1, pp), jnp.dtype(out_dtype) if out_dtype is not None else g.dtype)
    w_op = w.reshape(1, n).astype(jnp.float32)
    cparams = _compiler_params(n, bp, g.dtype.itemsize)
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    g_spec = pl.BlockSpec((n, bp), lambda i: (0, i))
    o_spec = pl.BlockSpec((1, bp), lambda i: (0, i))
    if mask is None:
        out = pl.pallas_call(
            _agg_kernel,
            name="masked_scaled_aggregate",
            grid=(pp // bp,),
            in_specs=[vec_spec, g_spec],
            out_specs=o_spec,
            out_shape=out_shape,
            compiler_params=cparams,
            interpret=interpret,
        )(w_op, g)
    else:
        m_op = mask.reshape(1, n).astype(jnp.float32)
        out = pl.pallas_call(
            _agg_kernel_masked,
            name="masked_scaled_aggregate_masked",
            grid=(pp // bp,),
            in_specs=[vec_spec, vec_spec, g_spec],
            out_specs=o_spec,
            out_shape=out_shape,
            compiler_params=cparams,
            interpret=interpret,
        )(w_op, m_op, g)
    return out[0, :p]


@functools.partial(jax.jit,
                   static_argnames=("block_p", "interpret", "out_dtype"))
def masked_scaled_aggregate_update_kernel(g, w, eta, params=None, mask=None,
                                          *, block_p: int = 2048,
                                          interpret: bool = False,
                                          out_dtype=None):
    """Fused reduce-and-update: one tiled launch over the parameter axis.

    g: (N, P); w: (N,); eta: scalar learning rate.

    * ``params`` given ((P,)): returns ``params − eta·(w_sel @ g)`` —
      the whole flat SGD server step (mask-select, per-client scaling,
      client-axis reduction, parameter update) as a single Pallas
      program. Output dtype is ``params.dtype`` unless ``out_dtype``
      overrides it.
    * ``params`` None: returns the update *delta* ``−eta·(w_sel @ g)``
      — the client-sharded form, where the (P,)-sized delta psums
      across shards before the replicated parameters absorb it
      (``out_dtype`` then defaults to f32 so partials travel in the
      accumulation dtype).

    ``mask`` is the (N,) 0/1 active-row operand; masked rows are
    zero-*selected* inside the tile before the MXU matvec (exact zeros
    even for inf/NaN garbage rows). In-kernel accumulation is f32
    regardless of input dtypes; ``eta`` rides a (1, 1) operand
    replicated to every grid step.
    """
    n, p = g.shape
    bp = min(block_p, p)
    pad = (-p) % bp
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad)))
    pp = p + pad
    if out_dtype is None:
        out_dtype = jnp.float32 if params is None else params.dtype
    out_shape = jax.ShapeDtypeStruct((1, pp), jnp.dtype(out_dtype))
    w_op = w.reshape(1, n).astype(jnp.float32)
    # mask=None runs the same program under an all-ones select — a
    # bit-exact identity on every row, unlike a ×mask multiplicand.
    m_op = (jnp.ones((1, n), jnp.float32) if mask is None
            else mask.reshape(1, n).astype(jnp.float32))
    eta_op = jnp.asarray(eta, jnp.float32).reshape(1, 1)
    cparams = _compiler_params(n, bp, g.dtype.itemsize)
    scalar_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    vec_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
    g_spec = pl.BlockSpec((n, bp), lambda i: (0, i))
    tile_spec = pl.BlockSpec((1, bp), lambda i: (0, i))
    if params is None:
        out = pl.pallas_call(
            _agg_delta_kernel,
            name="masked_scaled_aggregate_update_delta",
            grid=(pp // bp,),
            in_specs=[scalar_spec, vec_spec, vec_spec, g_spec],
            out_specs=tile_spec,
            out_shape=out_shape,
            compiler_params=cparams,
            interpret=interpret,
        )(eta_op, w_op, m_op, g)
    else:
        p_op = params.reshape(1, p)
        if pad:
            p_op = jnp.pad(p_op, ((0, 0), (0, pad)))
        out = pl.pallas_call(
            _agg_update_kernel,
            name="masked_scaled_aggregate_update",
            grid=(pp // bp,),
            in_specs=[scalar_spec, vec_spec, vec_spec, g_spec, tile_spec],
            out_specs=tile_spec,
            out_shape=out_shape,
            compiler_params=cparams,
            interpret=interpret,
        )(eta_op, w_op, m_op, g, p_op)
    return out[0, :p]
