"""Property tests for the ravel boundary (DESIGN.md §5/§8).

The flat gradient path starts at the ``grads_fn`` output —
:func:`repro.core.aggregation.flatten_client_grads` turns it into the
``(N, P)`` buffer the simulator step reduces, so these properties pin
the boundary itself: flatten/unflatten round-trip
identity over random nested pytree *structures* (not just flat dicts)
and mixed-dtype rejection, plus exact-zero contribution of masked rows
through the wrapped flat path even when the masked rows hold inf/NaN.

Skipped as a whole when ``hypothesis`` is absent from the container.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import aggregation  # noqa: E402

_shape = st.lists(st.integers(1, 5), min_size=0, max_size=3).map(tuple)

# Random *nested* pytree structures: leaves are shape tuples, nodes are
# dicts / tuples / lists.
_structure = st.recursive(
    _shape,
    lambda kids: st.one_of(
        st.dictionaries(st.sampled_from(list("abcdef")), kids,
                        min_size=1, max_size=3),
        st.lists(kids, min_size=1, max_size=3).map(tuple),
        st.lists(kids, min_size=1, max_size=3),
    ),
    max_leaves=6,
)


def _is_shape(x):
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def _build(structure, key, lead=(), dtypes=None):
    """Materialize a structure of shape-tuples into arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(structure, is_leaf=_is_shape)
    arrays = []
    for i, shp in enumerate(leaves):
        dt = jnp.float32 if dtypes is None else dtypes[i % len(dtypes)]
        arr = jax.random.normal(jax.random.fold_in(key, i), lead + shp)
        arrays.append(arr.astype(dt))
    return jax.tree_util.tree_unflatten(treedef, arrays)


@settings(max_examples=30, deadline=None)
@given(structure=_structure, seed=st.integers(0, 2**30))
def test_ravel_roundtrip_identity_random_structures(structure, seed):
    """flatten → unflatten is the identity (bitwise) for arbitrary
    nested dict/tuple/list pytrees, both the (P,) and (N, P) views."""
    key = jax.random.PRNGKey(seed)
    tree = _build(structure, key)
    spec = aggregation.ravel_spec(tree)
    vec = aggregation.ravel_pytree(tree, spec)
    assert vec.shape == (spec.total,)
    back = aggregation.unravel_pytree(vec, spec)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    stacked = _build(structure, jax.random.fold_in(key, 1), lead=(4,))
    sspec = aggregation.ravel_spec(stacked, lead_axes=1)
    flat = aggregation.ravel_stacked(stacked, sspec)
    assert flat.shape == (4, sspec.total)
    back = aggregation.unravel_pytree(flat, sspec)
    for a, b in zip(jax.tree_util.tree_leaves(stacked),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@settings(max_examples=20, deadline=None)
@given(structure=_structure, seed=st.integers(0, 2**30),
       dtypes=st.permutations([jnp.float32, jnp.bfloat16]))
def test_mixed_dtype_trees_are_rejected(structure, seed, dtypes):
    """A pytree mixing leaf dtypes cannot concatenate — ravel_spec must
    raise (the trainer then falls back to the per-leaf path)."""
    n_leaves = len(jax.tree_util.tree_leaves(structure, is_leaf=_is_shape))
    if n_leaves < 2:
        structure = (structure, ())
    tree = _build(structure, jax.random.PRNGKey(seed), dtypes=list(dtypes))
    with pytest.raises(ValueError, match="dtype"):
        aggregation.ravel_spec(tree)


@settings(max_examples=25, deadline=None)
@given(structure=_structure, seed=st.integers(0, 2**30),
       n=st.integers(2, 8), use_kernel=st.booleans())
def test_masked_rows_contribute_exact_zero_through_flat_grads_fn(
        structure, seed, n, use_kernel):
    """The flat grads_fn path end-to-end: flatten a stacked-pytree
    grads_fn output with flatten_client_grads, poison the masked-out client rows with
    inf/NaN, and require the reduction to be *bitwise* the reduction of
    the clean rows — the mask is a row select, not a multiply."""
    key = jax.random.PRNGKey(seed)
    params = _build(structure, key)
    spec = aggregation.ravel_spec(params)
    clean = _build(structure, jax.random.fold_in(key, 2), lead=(n,))
    mask = (jax.random.uniform(jax.random.fold_in(key, 3), (n,))
            < 0.6).astype(jnp.float32)
    mask = mask.at[0].set(1.0)  # at least one active row
    poison = jax.tree_util.tree_map(
        lambda x: jnp.where(
            mask.reshape((-1,) + (1,) * (x.ndim - 1)) > 0, x,
            jnp.full_like(x, jnp.inf) * jnp.where(x > 0, 1.0, jnp.nan)),
        clean)
    weights = jax.random.uniform(jax.random.fold_in(key, 4), (n,)) * mask

    g_clean = aggregation.flatten_client_grads(clean, spec, n)
    g_poison = aggregation.flatten_client_grads(poison, spec, n)
    assert g_clean.shape == g_poison.shape == (n, spec.total)

    ref = aggregation.reduce_flat(g_clean, weights, mask=mask)
    got = aggregation.reduce_flat(g_poison, weights, use_kernel=use_kernel,
                                  mask=mask)
    assert np.isfinite(np.asarray(got)).all()
    if use_kernel:
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=1e-6, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**30), n=st.integers(1, 6),
       dim=st.integers(1, 7))
def test_flat_grads_fn_array_output_is_the_ravel(seed, n, dim):
    """A grads_fn emitting a single (N, ...) array takes the natively
    flat fast path — bitwise the ravel of the equivalent pytree."""
    key = jax.random.PRNGKey(seed)
    params = jax.random.normal(key, (dim,))
    spec = aggregation.ravel_spec(params)
    stacked = jax.random.normal(jax.random.fold_in(key, 1), (n, dim))
    out = aggregation.flatten_client_grads(stacked, spec, n)
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(aggregation.ravel_stacked(stacked,
                                             aggregation.ravel_spec(
                                                 stacked, lead_axes=1))))
