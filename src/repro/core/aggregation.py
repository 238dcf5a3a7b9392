"""Server-side aggregation (paper eq. 11 / 12) — flat single-pass hot path.

The update the paper's server performs is

    w ← w − η · Σ_{i∈S_t} p_i · scale_i^t · g_i(w, ξ_i)

which we express as a *weighted sum over the client axis* with weights
``ω_i = p_i · mask_i · scale_i``. Execution paths, all algebraically
identical:

1. ``aggregate_client_grads`` — per-leaf weighted sum over the leading
   client axis, pure jnp. The *reference* path: no raveling, preserves
   every leaf dtype independently. Property tests compare everything
   else against it.
2. ``aggregate_client_grads_flat`` / ``aggregate_client_grads_kernel``
   — the hot path (DESIGN.md §5): the whole gradient pytree is raveled
   into **one** ``(N, P)`` buffer (a cached :class:`RavelSpec` records
   treedef/shapes/offsets), reduced by **one** tiled Pallas kernel or
   jnp matvec per step — instead of one kernel launch (each with its
   own lane padding) per parameter leaf — and unraveled by offset
   slicing. Mixed-dtype pytrees fall back to the per-leaf path.
   The simulator takes this path for gradients that arrive as one
   ``(N, ...)`` array, or that faults or a client mesh axis act on as
   flat rows (``psum``/``fused``); a multi-leaf gradient tree
   (:func:`reduces_leafwise`) is reduced by path 1 where it lies, after
   :func:`gather_client_rows` under ``gather``, and only its ``(P,)``
   aggregate is raveled, so no ``(N, P)`` copy of it is ever written.
3. ``per_example_coefficients`` — the *SPMD path* for framework-scale
   training: instead of materializing N per-client gradients, each example
   in the global batch carries the coefficient of its owning client, and
   the ordinary gradient of the weighted loss equals the paper's update.
   This is what the pjit train step uses; it adds **zero** collective
   traffic over plain data-parallel SGD.

The raveler is shared infrastructure: :class:`repro.core.trainer.
ClientSimulator` keeps its whole scan carry (params + optimizer state)
in the flat space, so the per-step loop never round-trips the pytree
leaf-by-leaf. The ravel boundary itself sits at the gradient source —
:func:`flatten_client_grads` turns any ``grads_fn`` output into a flat
``(N, P)`` buffer, and :func:`shard_client_grads` calls a ``grads_fn``
for one shard of a client mesh axis (DESIGN.md §8);
:func:`reduce_flat_client_sharded` is the cross-shard reduction with the
server update left replicated.
"""

from __future__ import annotations

import inspect
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.scheduling import Decision


def client_weights(p: jax.Array, decision: Decision) -> jax.Array:
    """ω_i = p_i · mask_i · scale_i — the per-client aggregation weight."""
    return p * decision.mask * decision.scale


# ----------------------------------------------------- reduction grammar

_REDUCTION_MODES = ("gather", "psum", "fused")


def parse_reduction(reduction: str) -> tuple[str, Any]:
    """Parse a cross-shard reduction string → ``(mode, wire_dtype)``.

    Grammar (DESIGN.md §9): ``gather | psum | psum_bf16 | fused |
    fused_bf16``. The optional ``_bf16`` suffix quantizes the ``(P,)``
    partial sums *on the wire only* — each shard's partial is cast to
    bf16, gathered, and accumulated locally in f32 (quantize-then-
    exact-accumulate; a plain psum of bf16 operands would accumulate in
    bf16 and compound rounding with shard count). ``gather`` admits no
    wire dtype: it is the bit-for-bit differential oracle, and rounding
    the wire would contradict that contract.
    """
    mode, _, wire = reduction.partition("_")
    if mode not in _REDUCTION_MODES or wire not in ("", "bf16"):
        raise ValueError(
            f"reduction must be one of gather, psum[_bf16], fused[_bf16]; "
            f"got {reduction!r}")
    if wire and mode == "gather":
        raise ValueError(
            "gather is the bitwise oracle and takes no wire dtype; "
            f"got {reduction!r}")
    return mode, (jnp.bfloat16 if wire else None)


def _mask_rows(leaf: jax.Array, mask: jax.Array | None) -> jax.Array:
    """Zero the masked-out client rows of an (N, ...) buffer.

    A ``where`` select, not a multiply: padded rows contribute *exact*
    zeros to every reduction even when a grads_fn emits garbage
    (inf/NaN) for clients that don't exist (DESIGN.md §7). Identity on
    active rows, so the masked reduction stays bit-identical to the
    unpadded one.
    """
    if mask is None:
        return leaf
    m = mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
    return jnp.where(m > 0, leaf, jnp.zeros((), leaf.dtype))


def compose_masks(*masks):
    """Product of (N,) 0/1 row masks, None-transparent.

    ``None`` means "no constraint" and drops out; all-None composes to
    None (the unmasked fast path). With 0/1 operands the product is
    exact — a row survives iff every mask keeps it — so composing the
    ragged ``active_mask`` with a fault-delivery ``keep`` mask preserves
    the DESIGN.md §7 guarantee: a row dropped by *either* contributes an
    exact zero through :func:`_mask_rows` / the masked Pallas kernels.
    """
    out = None
    for m in masks:
        if m is None:
            continue
        out = m if out is None else out * m
    return out


# --------------------------------------------------------------- raveler

class RavelSpec(NamedTuple):
    """Static flat-space layout of a pytree: where each leaf lives in P.

    ``shapes`` exclude any leading batch axes (``lead_axes`` at build
    time), so one spec describes both the stacked ``(N, P)`` gradient
    buffer and the unbatched ``(P,)`` parameter vector of the same tree.
    """

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    offsets: tuple[int, ...]
    sizes: tuple[int, ...]
    dtype: Any
    total: int


_SPEC_CACHE: dict = {}


def ravel_spec(tree, *, lead_axes: int = 0) -> RavelSpec:
    """Cached flat-space spec for ``tree``.

    ``lead_axes`` axes are stripped from every leaf shape (1 for
    client-stacked gradients). Raises ``ValueError`` on mixed leaf
    dtypes — the flat buffer is a single concatenation, so callers fall
    back to the per-leaf path (see :func:`aggregate_client_grads_flat`).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        raise ValueError("cannot ravel an empty pytree")
    shapes = tuple(tuple(l.shape[lead_axes:]) for l in leaves)
    dtypes = {jnp.dtype(l.dtype) for l in leaves}
    if len(dtypes) != 1:
        raise ValueError(
            f"flat path needs a single leaf dtype, got {sorted(map(str, dtypes))}")
    dtype = dtypes.pop()
    key = (treedef, shapes, dtype)
    spec = _SPEC_CACHE.get(key)
    if spec is None:
        sizes = tuple(math.prod(s) for s in shapes)
        offsets, off = [], 0
        for sz in sizes:
            offsets.append(off)
            off += sz
        spec = RavelSpec(treedef=treedef, shapes=shapes, offsets=tuple(offsets),
                         sizes=sizes, dtype=dtype, total=off)
        _SPEC_CACHE[key] = spec
    return spec


def ravel_pytree(tree, spec: RavelSpec | None = None) -> jax.Array:
    """Concatenate every leaf of ``tree`` into one ``(P,)`` vector."""
    if spec is None:
        spec = ravel_spec(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    if len(leaves) == 1:
        return leaves[0].reshape(-1)
    return jnp.concatenate([l.reshape(-1) for l in leaves])


def ravel_stacked(tree, spec: RavelSpec | None = None) -> jax.Array:
    """Client-stacked pytree (leaves ``(N, ...)``) → one ``(N, P)`` buffer."""
    if spec is None:
        spec = ravel_spec(tree, lead_axes=1)
    leaves = jax.tree_util.tree_leaves(tree)
    n = leaves[0].shape[0]
    if len(leaves) == 1:
        return leaves[0].reshape(n, -1)
    return jnp.concatenate([l.reshape(n, -1) for l in leaves], axis=1)


def unravel_pytree(vec: jax.Array, spec: RavelSpec):
    """``(..., P)`` flat vector → pytree with leaves ``(..., *shape)``."""
    lead = vec.shape[:-1]
    parts = [
        vec[..., o:o + sz].reshape(lead + shp)
        for o, sz, shp in zip(spec.offsets, spec.sizes, spec.shapes)
    ]
    return jax.tree_util.tree_unflatten(spec.treedef, parts)


# ------------------------------------------------ flat grads_fn boundary

def accepts_clients_kwarg(grads_fn) -> bool:
    """True if ``grads_fn`` takes a ``clients`` keyword — the client-axis
    sharding protocol (DESIGN.md §8): a client-aware grads_fn is called
    with ``clients=(n_local,) int32`` global client indices and computes
    only those rows, so per-client gradient work shards across devices.
    A plain ``(params, key, t)`` grads_fn still works sharded — each
    device computes the full stack and slices its rows (correct and
    bitwise-identical, but the gradient compute is replicated). Only an
    explicitly *named* ``clients`` parameter opts in — a bare
    ``**kwargs`` does not, since a kwargs-tolerant grads_fn that ignores
    ``clients`` would silently return full-population rows."""
    try:
        sig = inspect.signature(grads_fn)
    except (TypeError, ValueError):
        return False
    return "clients" in sig.parameters


def flatten_client_grads(stacked, spec: RavelSpec, n_rows: int) -> jax.Array:
    """Client-stacked ``grads_fn`` output → one flat ``(n_rows, P)``
    buffer in the layout of ``spec``.

    The grads_fn may emit a client-stacked pytree mirroring the
    parameter tree (raveled via its spec; uniform-dtype gradient trees
    that differ from the params dtype stay in their own dtype,
    mixed-dtype trees are cast to the params dtype — accumulation in
    the reduce is f32-or-better either way), or a single ``(N, ...)``
    array — already flat up to a reshape (the natively-flat fast path;
    single-leaf parameter trees land here).
    """
    if isinstance(stacked, jax.Array):
        g = stacked.reshape(n_rows, -1)
        if g.shape[1] != spec.total:
            raise ValueError(
                f"flat grads_fn output has {g.shape[1]} parameters per "
                f"client; the parameter pytree has {spec.total}")
        return g
    try:
        gspec = ravel_spec(stacked, lead_axes=1)
    except ValueError:
        # Mixed-dtype gradients (e.g. one layer computed in bf16)
        # against uniform-dtype params: aggregate in the params
        # dtype — accumulation inside reduce_flat is f32-or-better
        # either way.
        stacked = jax.tree_util.tree_map(
            lambda x: x.astype(spec.dtype), stacked)
        gspec = ravel_spec(stacked, lead_axes=1)
    if gspec.shapes != spec.shapes or gspec.treedef != spec.treedef:
        raise ValueError(
            "grads_fn output does not mirror the parameter pytree; "
            "flat-carry execution needs matching structure+shapes "
            f"(params {spec.shapes}, grads {gspec.shapes})")
    return ravel_stacked(stacked, gspec)


def reduces_leafwise(stacked, spec: RavelSpec) -> bool:
    """Whether client-stacked gradients are reduced leaf by leaf.

    True for a float32 tree of more than one leaf that mirrors the
    float32 parameter layout ``spec``: :func:`aggregate_client_grads`
    then reduces each leaf where it lies, and only the ``(P,)``
    aggregate is raveled. Raveling such a tree into ``(N, P)`` costs a
    copy of every leaf (a relayout of transposed leaves, then unaligned
    writes at the leaf offsets) before the reduction reads it once. A
    single array is already flat up to a free reshape; other or mixed
    dtypes take the flat buffer, which casts and upcasts them.
    """
    leaves, treedef = jax.tree_util.tree_flatten(stacked)
    return (len(leaves) > 1 and treedef == spec.treedef
            and spec.dtype == jnp.float32
            and all(leaf.dtype == spec.dtype and leaf.shape[1:] == shape
                    for leaf, shape in zip(leaves, spec.shapes)))


def shard_client_grads(grads_fn, params, key, t, n_clients: int, shard):
    """This shard's client-stacked gradients under a clients mesh axis
    (DESIGN.md §8), as ``(stacked, n_rows)``.

    A client-aware grads_fn (:func:`accepts_clients_kwarg`) is called
    with the shard's global client indices and returns its ``n_local``
    rows; a plain grads_fn is called in full — row-sliced (bitwise the
    rows of the unsharded call) under ``psum``/``fused``, but handed
    over *whole* under ``gather``, where every shard already holds the
    identical replicated rows and slicing them apart only for
    ``all_gather`` to reassemble them would be a pure N·P round trip
    (:func:`gather_client_rows` skips the gather on full-width rows).
    """
    n_local = n_clients // shard.shards
    if accepts_clients_kwarg(grads_fn):
        idx = (jax.lax.axis_index(shard.axis_name) * n_local
               + jnp.arange(n_local, dtype=jnp.int32))
        return grads_fn(params, key, t, clients=idx), n_local
    full = grads_fn(params, key, t)
    if parse_reduction(shard.reduction)[0] == "gather":
        return full, n_clients
    off = jax.lax.axis_index(shard.axis_name) * n_local
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, off, n_local, axis=0),
        full), n_local


def gather_client_rows(stacked, weights: jax.Array, mask, axis_name: str):
    """Reassemble the global client rows of ``gather`` (DESIGN.md §8):
    all_gather (tiled, so in exact row order) the weights, the mask and
    every gradient leaf not already at full population width, so each
    shard can replay the identical unsharded reduction."""
    weights = jax.lax.all_gather(weights, axis_name, axis=0, tiled=True)
    if mask is not None:
        mask = jax.lax.all_gather(mask, axis_name, axis=0, tiled=True)
    stacked = jax.tree_util.tree_map(
        lambda x: x if x.shape[0] == weights.shape[0] else
        jax.lax.all_gather(x, axis_name, axis=0, tiled=True), stacked)
    return stacked, weights, mask


# ----------------------------------------------------- aggregation paths

def aggregate_client_grads(stacked_grads, weights: jax.Array,
                           mask: jax.Array | None = None):
    """Per-leaf weighted sum over the leading (client) axis — the
    reference path (one reduction per leaf, leaf dtypes preserved).

    stacked_grads: pytree whose leaves have shape (N, ...).
    weights: (N,) float32 — ω_i.
    mask: optional (N,) 0/1 active-client mask; masked rows are
        ``where``-selected to exact zero before the reduction.
    """

    def _one(leaf):
        w = weights.reshape((-1,) + (1,) * (leaf.ndim - 1)).astype(leaf.dtype)
        return jnp.sum(w * _mask_rows(leaf, mask), axis=0)

    return jax.tree_util.tree_map(_one, stacked_grads)


def reduce_flat(g: jax.Array, weights: jax.Array, *,
                use_kernel: bool = False, out_dtype=None,
                mask: jax.Array | None = None) -> jax.Array:
    """``(N, P)`` flat gradient buffer → ``(P,)`` = ω @ g, in one pass.

    Accumulation is at least f32 (low-precision inputs are upcast; f64
    under ``jax_enable_x64`` stays f64). ``out_dtype`` overrides the
    result dtype — e.g. bf16 client gradients aggregated into an f32
    server update without a round-trip through bf16. ``mask`` is the
    (N,) active-client mask of a ragged population: masked rows are
    excluded from the reduction *exactly* (a row select, not a ×0 — the
    kernel takes the mask as an operand on the tiled reduction). The
    Pallas path is one tiled kernel launch over the whole parameter
    space (imported lazily so the pure-jnp path has no kernel
    dependency); in-kernel accumulation is f32 — the MXU contract.
    """
    od = jnp.dtype(out_dtype) if out_dtype is not None else g.dtype
    if use_kernel:
        from repro.kernels.aggregate import ops as agg_ops

        return agg_ops.masked_scaled_aggregate(
            g, weights.astype(jnp.float32), out_dtype=od, mask=mask)
    acc = jnp.promote_types(g.dtype, jnp.float32)
    # HIGHEST: the TPU's default matmul precision rounds f32 operands to
    # bf16, which would break the f32 contract above (and the kernel's).
    out = jnp.matmul(weights.astype(acc), _mask_rows(g, mask).astype(acc),
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(od)


def _cross_shard_sum(partial: jax.Array, axis_name: str,
                     wire_dtype=None) -> jax.Array:
    """Sum ``(P,)`` partials across ``axis_name`` shards.

    ``wire_dtype=None`` is a plain psum. With a wire dtype (bf16), each
    shard's partial is *quantized once* for the collective, then the
    gathered partials are accumulated locally in f32-or-better — so the
    rounding error is one cast per shard, independent of shard count. A
    psum of bf16 operands would instead accumulate in bf16, compounding
    rounding with every add in the reduction tree.
    """
    if wire_dtype is None:
        return jax.lax.psum(partial, axis_name)
    acc = jnp.promote_types(partial.dtype, jnp.float32)
    wired = jax.lax.all_gather(partial.astype(wire_dtype), axis_name, axis=0)
    return jnp.sum(wired.astype(acc), axis=0)


def reduce_flat_client_sharded(g: jax.Array, weights: jax.Array, *,
                               axis_name: str, reduction: str = "gather",
                               use_kernel: bool = False, out_dtype=None,
                               mask: jax.Array | None = None,
                               wire_dtype=None
                               ) -> tuple[jax.Array, jax.Array]:
    """Client-sharded flat reduction: local ``(n_local, P)`` shard →
    replicated ``((P,), weight_sum)`` across the ``axis_name`` devices.

    Two reductions, both leaving the server update replicated
    (DESIGN.md §8):

    * ``"gather"`` — all_gather the gradient rows (tiled, so the global
      ``(N, P)`` buffer is reassembled in exact row order) and replicate
      the *identical* unsharded reduction on every device. Bit-for-bit
      the single-device result; costs N_local·P per device per step of
      interconnect. A ``g`` already at full population width (a plain
      grads_fn computes it replicated on every shard —
      :func:`shard_client_grads`) skips the gradient gather; only the
      (N,)-sized weights/mask cross the axis.
    * ``"psum"`` — one local matvec/kernel launch over this shard's rows
      followed by a ``(P,)`` cross-shard sum. Bandwidth-optimal (the
      collective moves P floats, not N·P) but reassociates the client
      sum across shards — float32-tolerance, not bitwise. Partial sums
      travel in the f32-or-better accumulation dtype and are cast to
      ``out_dtype`` only after the collective. ``"psum_bf16"`` (or an
      explicit ``wire_dtype``) additionally quantizes the partials to
      bf16 *on the wire only* — local accumulation stays f32 on both
      sides of the collective (:func:`_cross_shard_sum`), halving
      collective bytes for one rounding per shard.

    ``"fused"`` is rejected here: the fused reduce-and-update owns the
    parameter step as well and lives in :func:`fused_flat_sgd_update`.
    """
    mode, parsed_wire = parse_reduction(reduction)
    if wire_dtype is None:
        wire_dtype = parsed_wire
    if mode == "fused":
        raise ValueError(
            "reduction 'fused' bundles the parameter update; use "
            "fused_flat_sgd_update (trainer routes it automatically)")
    if mode == "gather":
        if wire_dtype is not None:
            raise ValueError("gather is bitwise; wire_dtype is not allowed")
        g, weights, mask = gather_client_rows(g, weights, mask, axis_name)
        out = reduce_flat(g, weights, use_kernel=use_kernel,
                          out_dtype=out_dtype, mask=mask)
        return out, jnp.sum(weights)
    od = jnp.dtype(out_dtype) if out_dtype is not None else g.dtype
    acc = jnp.promote_types(g.dtype, jnp.float32)
    partial = reduce_flat(g, weights, use_kernel=use_kernel,
                          out_dtype=acc, mask=mask)
    out = _cross_shard_sum(partial, axis_name, wire_dtype).astype(od)
    return out, jax.lax.psum(jnp.sum(weights), axis_name)


def fused_flat_sgd_update(g: jax.Array, weights: jax.Array,
                          params: jax.Array, opt_state, optimizer, *,
                          mask: jax.Array | None = None,
                          use_kernel: bool = False, shard=None,
                          wire_dtype=None):
    """Fused reduce-and-update (DESIGN.md §9): mask-select, per-client
    scaling, ``(N, P) → (P,)`` reduction, and the flat SGD parameter
    step in **one** pass — a single Pallas launch when ``use_kernel``
    (``masked_scaled_aggregate_update``), a single XLA-fusable matvec +
    axpy otherwise. Returns ``(new_params, new_opt_state, weight_sum)``.

    Only engages for a tagged plain-SGD optimizer (``kind == "sgd"``) —
    the kernel reproduces ``w − η·(ω_sel @ g)`` exactly; anything
    stateful (momentum, Adam) or nonlinear in the gradient (clipping)
    must keep the unfused reduce → update split.

    Sharded (``shard`` a ``ClientShard``): each device's kernel emits
    its local update *delta* ``−η·(ω_sel @ g_local)``; SGD is linear in
    the gradient, so ``params + Σ_shards delta`` equals the update of
    the global reduction. The collective stays ``(P,)``-sized
    (:func:`_cross_shard_sum`; ``wire_dtype`` quantizes it bf16-on-the-
    wire with f32 accumulation), and the replicated parameters absorb
    the summed delta in f32 before casting back.
    """
    from repro.optim.optimizers import SGDState, resolve_lr

    if getattr(optimizer, "kind", "") != "sgd":
        raise ValueError(
            "fused_flat_sgd_update requires a plain sgd() optimizer "
            f"(kind='sgd'); got kind={getattr(optimizer, 'kind', '')!r}")
    eta = resolve_lr(optimizer.hyper, opt_state.step)
    new_state = SGDState(step=opt_state.step + 1)
    w32 = weights.astype(jnp.float32)
    if shard is None:
        if use_kernel:
            from repro.kernels.aggregate import ops as agg_ops

            new_params = agg_ops.masked_scaled_aggregate_update(
                g, w32, eta, params, mask)
        else:
            agg = reduce_flat(g, weights, out_dtype=jnp.float32, mask=mask)
            new_params = (params.astype(jnp.float32)
                          - eta * agg).astype(params.dtype)
        return new_params, new_state, jnp.sum(weights)
    if use_kernel:
        from repro.kernels.aggregate import ops as agg_ops

        delta = agg_ops.masked_scaled_aggregate_update(g, w32, eta, None, mask)
    else:
        agg = reduce_flat(g, weights, out_dtype=jnp.float32, mask=mask)
        delta = -eta * agg
    delta = _cross_shard_sum(delta, shard.axis_name, wire_dtype)
    new_params = (params.astype(jnp.float32) + delta).astype(params.dtype)
    wsum = jax.lax.psum(jnp.sum(weights), shard.axis_name)
    return new_params, new_state, wsum


def aggregate_client_grads_flat(stacked_grads, weights: jax.Array, *,
                                use_kernel: bool = False,
                                mask: jax.Array | None = None):
    """Single-pass aggregation: ravel → one kernel/matvec → unravel.

    Same contract as :func:`aggregate_client_grads` (float32-accumulation
    tolerance); issues exactly **one** reduction regardless of the number
    of parameter leaves. Mixed-dtype pytrees fall back to the per-leaf
    path.
    """
    try:
        spec = ravel_spec(stacked_grads, lead_axes=1)
    except ValueError:
        if use_kernel:
            return aggregate_client_grads_kernel_per_leaf(
                stacked_grads, weights, mask)
        return aggregate_client_grads(stacked_grads, weights, mask)
    g = ravel_stacked(stacked_grads, spec)
    return unravel_pytree(
        reduce_flat(g, weights, use_kernel=use_kernel, mask=mask), spec)


def aggregate_client_grads_kernel(stacked_grads, weights: jax.Array,
                                  mask: jax.Array | None = None):
    """Kernel-path aggregation: one Pallas launch for the whole pytree.

    Previously one ``masked_scaled_aggregate`` call (with its own lane
    padding) *per leaf*; now the tree is raveled once into ``(N, P)``
    and reduced by a single tiled kernel (DESIGN.md §5).
    """
    return aggregate_client_grads_flat(stacked_grads, weights,
                                       use_kernel=True, mask=mask)


def aggregate_client_grads_kernel_per_leaf(stacked_grads, weights: jax.Array,
                                           mask: jax.Array | None = None):
    """One kernel launch per leaf — the pre-flat kernel path, kept as
    the mixed-dtype fallback and the ``ClientSimulator(flat=False)``
    legacy behavior."""
    from repro.kernels.aggregate import ops as agg_ops

    def _one(leaf):
        n = leaf.shape[0]
        flat = leaf.reshape(n, -1)
        out = agg_ops.masked_scaled_aggregate(
            flat, weights.astype(leaf.dtype), mask=mask)
        return out.reshape(leaf.shape[1:])

    return jax.tree_util.tree_map(_one, stacked_grads)


def per_example_coefficients(
    client_ids: jax.Array,
    weights: jax.Array,
    examples_per_client: jax.Array | int,
) -> jax.Array:
    """Per-example loss coefficients realizing the paper's update in SPMD.

    If client i owns b_i examples of the batch and g_i is the *mean*
    gradient over its examples, then

        Σ_i ω_i g_i = Σ_i Σ_{j∈i} (ω_i / b_i) · ∇l_ij

    so example j of client i gets coefficient ω_i / b_i. Gradient of
    ``sum(coeff * per_example_loss)`` == paper's aggregated update.

    client_ids : (B,) int32 — owning client of each example.
    weights    : (N,) float32 — ω_i.
    examples_per_client : scalar or (N,) — b_i.
    """
    b = jnp.asarray(examples_per_client, jnp.float32)
    if b.ndim == 0:
        per_client = weights / b
    else:
        per_client = weights / jnp.maximum(b, 1.0)
    return per_client[client_ids]


def server_update(params, aggregated_grads, lr):
    """Plain SGD server update, w ← w − η · aggregate (paper eq. 11)."""
    return jax.tree_util.tree_map(
        lambda w, g: w - lr * g.astype(w.dtype), params, aggregated_grads
    )
