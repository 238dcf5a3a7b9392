"""Flat single-pass aggregation tests (DESIGN.md §5).

Covers the shared raveler (ravel → reduce → unravel ≡ per-leaf
reference, across ragged leaf shapes that previously forced per-leaf
kernel padding), the one-kernel-call-per-step guarantee, mixed-dtype
fallback, and flat-carry simulator equivalence. Randomized-shape
property tests ride the hypothesis importorskip pattern of
``test_kernels_properties.py`` via plain parametrization here so the
module always runs.
"""

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import ClientSimulator, aggregation, make_quadratic, make_scheduler
from repro.core.energy import BinaryArrivals, DeterministicArrivals
from repro.core.faults import DropUpdates
from repro.kernels.aggregate import ops as agg_ops
from repro.optim import adam, sgd

#: Ragged leaf layouts with odd (non-lane-aligned) sizes — each leaf
#: would previously get its own kernel launch and its own padding.
RAGGED_TREES = [
    {"w": (3, 5), "b": (7,), "k": (2, 3, 5)},
    {"a": (1,), "z": (13,), "m": (3, 1, 2)},
    {"only": (129,)},
    {"s": (), "v": (31,), "c": (5, 5)},
]


def _make_stacked(shapes: dict, n: int, seed: int):
    key = jax.random.PRNGKey(seed)
    tree = {}
    for i, (name, shp) in enumerate(sorted(shapes.items())):
        tree[name] = jax.random.normal(jax.random.fold_in(key, i),
                                       (n,) + shp, jnp.float32)
    return tree


# ------------------------------------------------------------- raveler

@pytest.mark.parametrize("shapes", RAGGED_TREES)
def test_ravel_unravel_roundtrip(shapes):
    tree = _make_stacked(shapes, 4, 0)
    spec = aggregation.ravel_spec(tree, lead_axes=1)
    assert spec.total == sum(np.prod(s, dtype=int) for s in spec.shapes)
    flat = aggregation.ravel_stacked(tree, spec)
    assert flat.shape == (4, spec.total)
    back = aggregation.unravel_pytree(flat, spec)
    assert jax.tree_util.tree_structure(back) == \
        jax.tree_util.tree_structure(tree)
    for name in tree:
        np.testing.assert_array_equal(np.asarray(tree[name]),
                                      np.asarray(back[name]))


def test_ravel_spec_is_cached():
    tree = _make_stacked(RAGGED_TREES[0], 4, 0)
    assert aggregation.ravel_spec(tree, lead_axes=1) is \
        aggregation.ravel_spec(tree, lead_axes=1)


def test_ravel_spec_rejects_mixed_dtypes_and_empty():
    with pytest.raises(ValueError, match="single leaf dtype"):
        aggregation.ravel_spec(
            {"a": jnp.zeros((2,), jnp.float32),
             "b": jnp.zeros((2,), jnp.bfloat16)})
    with pytest.raises(ValueError, match="empty"):
        aggregation.ravel_spec({})


# ----------------------------------------- flat ≡ per-leaf equivalence

@pytest.mark.parametrize("shapes", RAGGED_TREES)
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["matvec", "kernel"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_flat_matches_per_leaf_reference(shapes, use_kernel, seed):
    """ravel → one kernel/matvec → unravel ≡ per-leaf
    aggregate_client_grads, to float32 tolerance, across ragged leaves."""
    n = 6
    stacked = _make_stacked(shapes, n, seed)
    w = jax.random.uniform(jax.random.PRNGKey(100 + seed), (n,)) \
        * jnp.array([1.0, 0.0, 1.0, 1.0, 0.0, 1.0])  # masked clients
    ref = aggregation.aggregate_client_grads(stacked, w)
    got = aggregation.aggregate_client_grads_flat(stacked, w,
                                                  use_kernel=use_kernel)
    for name in ref:
        np.testing.assert_allclose(np.asarray(ref[name]),
                                   np.asarray(got[name]),
                                   rtol=2e-5, atol=2e-5)


def test_kernel_path_is_flat_single_call(monkeypatch):
    """aggregate_client_grads_kernel must issue exactly ONE kernel call
    for a multi-leaf pytree (previously one per leaf)."""
    calls = []
    real = agg_ops.masked_scaled_aggregate

    def counting(g, w, *a, **kw):
        calls.append(g.shape)
        return real(g, w, *a, **kw)

    monkeypatch.setattr(agg_ops, "masked_scaled_aggregate", counting)
    stacked = _make_stacked(RAGGED_TREES[0], 4, 0)
    total = sum(int(np.prod(s)) for s in RAGGED_TREES[0].values())
    aggregation.aggregate_client_grads_kernel(stacked, jnp.ones((4,)) / 4)
    assert calls == [(4, total)]


@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["matvec", "kernel"])
def test_reduce_flat_out_dtype_bf16_to_f32(use_kernel):
    """bf16 client gradients aggregate into an f32 server update without
    a round-trip through bf16 (out_dtype override, both backends)."""
    n, p = 5, 37
    g = jax.random.normal(jax.random.PRNGKey(0), (n, p)).astype(jnp.bfloat16)
    w = jax.random.uniform(jax.random.PRNGKey(1), (n,))
    out = aggregation.reduce_flat(g, w, use_kernel=use_kernel,
                                  out_dtype=jnp.float32)
    assert out.dtype == jnp.float32
    ref = w @ np.asarray(g, np.float32)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-2, atol=2e-2)


def test_mixed_dtype_falls_back_per_leaf():
    n = 4
    stacked = {
        "f32": jax.random.normal(jax.random.PRNGKey(0), (n, 5), jnp.float32),
        "bf16": jax.random.normal(jax.random.PRNGKey(1), (n, 3)
                                  ).astype(jnp.bfloat16),
    }
    w = jnp.array([0.5, 0.25, 0.0, 0.25])
    ref = aggregation.aggregate_client_grads(stacked, w)
    for use_kernel in (False, True):
        got = aggregation.aggregate_client_grads_flat(stacked, w,
                                                      use_kernel=use_kernel)
        assert got["f32"].dtype == jnp.float32
        assert got["bf16"].dtype == jnp.bfloat16
        for name in ref:
            np.testing.assert_allclose(
                np.asarray(ref[name], np.float32),
                np.asarray(got[name], np.float32), rtol=2e-2, atol=2e-2)


# ------------------------------------------- simulator flat-carry loop

def _dict_problem(n=4):
    shapes = {"w": (3, 5), "b": (7,), "k": (2, 3, 5)}
    params = {name: jnp.full(shp, 0.5) for name, shp in shapes.items()}
    target = _make_stacked(shapes, 1, 9)

    def grads_fn(p, key, t):
        # Per-client noisy pull toward a fixed target; N stacked leaves.
        noise = _make_stacked(shapes, n, 3)
        return jax.tree_util.tree_map(
            lambda pl, tg, nz: jnp.broadcast_to(pl - tg[0], (n,) + pl.shape)
            + 0.01 * nz, p, target, noise)

    def loss_fn(p):
        return sum(jnp.sum((pl - tg[0]) ** 2)
                   for pl, tg in zip(jax.tree_util.tree_leaves(p),
                                     jax.tree_util.tree_leaves(target)))

    return params, grads_fn, loss_fn


@pytest.mark.parametrize("opt", [sgd(0.05), adam(0.05)], ids=["sgd", "adam"])
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["matvec", "kernel"])
def test_flat_carry_run_matches_legacy(opt, use_kernel):
    """flat=None (auto) scan carry ≡ flat=False per-leaf carry, for both
    aggregation backends and a stateful optimizer (state in flat space)."""
    n = 4
    params, grads_fn, loss_fn = _dict_problem(n)
    mk = lambda flat: ClientSimulator(
        grads_fn=grads_fn, scheduler=make_scheduler("alg1", n),
        energy=DeterministicArrivals.periodic([1, 2, 4, 8], horizon=40),
        p=jnp.full((n,), 0.25), optimizer=opt, loss_fn=loss_fn,
        use_kernel=use_kernel, flat=flat)
    w_flat, h_flat = mk(None).run(jax.random.PRNGKey(2), params, 25)
    w_leaf, h_leaf = mk(False).run(jax.random.PRNGKey(2), params, 25)
    np.testing.assert_allclose(np.asarray(h_flat.loss),
                               np.asarray(h_leaf.loss),
                               rtol=2e-4, atol=1e-5)
    for name in w_flat:
        assert w_flat[name].shape == params[name].shape
        np.testing.assert_allclose(np.asarray(w_flat[name]),
                                   np.asarray(w_leaf[name]),
                                   rtol=2e-4, atol=1e-5)


def _gradient_source(arrives: str, n: int):
    """``(params, grads_fn, faults)`` of the dict problem with its client
    gradients delivered as the parameter tree, as one ``(N, P)`` array,
    or as the tree under a (rate-0) fault component."""
    params, grads_fn, _ = _dict_problem(n)
    if arrives == "array":
        return params, (lambda p, k, t: aggregation.ravel_stacked(
            grads_fn(p, k, t))), None
    return params, grads_fn, (DropUpdates(0.0) if arrives == "faults"
                              else None)


def _jaxpr_values(jaxpr) -> list:
    """``(primitive, shape)`` of every value an equation of ``jaxpr`` (or
    of a jaxpr nested in one) produces."""
    values = []
    for eqn in jaxpr.eqns:
        values += [(eqn.primitive.name, tuple(v.aval.shape))
                   for v in eqn.outvars]
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(
                        x, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    values += _jaxpr_values(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    values += _jaxpr_values(sub)
    return values


def _kernel_launches(monkeypatch, opt, arrives: str):
    """Trace a ``use_kernel=True`` run of the dict problem with ``opt``
    and gradients arriving as ``arrives``; returns the ``(N, P)`` shape,
    the shapes the fused / unfused kernel entries were called with, and
    ``(primitive, shape)`` of every value of the traced run."""
    calls = {"fused": [], "unfused": []}
    for name, entry in (("fused", "masked_scaled_aggregate_update"),
                        ("unfused", "masked_scaled_aggregate")):
        real = getattr(agg_ops, entry)

        def counting(g, w, *a, _real=real, _calls=calls[name], **kw):
            _calls.append(g.shape)
            return _real(g, w, *a, **kw)

        monkeypatch.setattr(agg_ops, entry, counting)
    n = 4
    params, grads_fn, faults = _gradient_source(arrives, n)
    sim = ClientSimulator(
        grads_fn=grads_fn, scheduler=make_scheduler("alg1", n),
        energy=BinaryArrivals([0.5] * n), p=jnp.full((n,), 0.25),
        optimizer=opt, faults=faults, use_kernel=True)
    jaxpr = jax.make_jaxpr(lambda k, p0: sim.run(k, p0, 10))(
        jax.random.PRNGKey(0), params)
    total = 3 * 5 + 7 + 2 * 3 * 5
    return (n, total), calls, _jaxpr_values(jaxpr.jaxpr)


@pytest.mark.parametrize("arrives", ["tree", "array", "faults"])
def test_flat_carry_one_kernel_call_per_step(monkeypatch, arrives):
    """With use_kernel=True and a tagged plain sgd() optimizer, gradients
    that arrive flat (one array, or rows a fault component acts on) take
    exactly one fused reduce-and-update launch over ``(N, P)`` per step
    (DESIGN.md §9) and never the unfused reduce. A gradient tree is
    reduced leaf by leaf: no Pallas launch, and no ``(N, P)`` value
    anywhere in the traced step."""
    np_shape, calls, values = _kernel_launches(monkeypatch, sgd(0.05),
                                               arrives)
    assert calls["unfused"] == []
    if arrives == "tree":
        assert calls["fused"] == []
        assert not [v for v in values
                    if v[0] == "pallas_call" or v[1] == np_shape]
    else:
        # The scan body traces once; a per-leaf implementation would
        # record len(params) == 3 shapes here.
        assert calls["fused"] == [np_shape]


@pytest.mark.parametrize("arrives", ["tree", "array", "faults"])
def test_flat_carry_stateful_optimizer_keeps_unfused_kernel(monkeypatch,
                                                            arrives):
    """adam (untagged) must keep the reduce → update split: gradients
    that arrive flat take exactly one unfused kernel launch per step,
    never the fused sgd op; a gradient tree takes no launch and makes
    no ``(N, P)`` value."""
    np_shape, calls, values = _kernel_launches(monkeypatch, adam(0.05),
                                               arrives)
    assert calls["fused"] == []
    if arrives == "tree":
        assert calls["unfused"] == []
        assert not [v for v in values
                    if v[0] == "pallas_call" or v[1] == np_shape]
    else:
        assert calls["unfused"] == [np_shape]


@pytest.mark.parametrize("arrives", ["tree", "array", "faults"])
def test_leafwise_update_is_counted_on_the_tree_path_only(arrives):
    n = 4
    params, grads_fn, faults = _gradient_source(arrives, n)
    sim = ClientSimulator(
        grads_fn=grads_fn, scheduler=make_scheduler("alg1", n),
        energy=BinaryArrivals([0.5] * n), p=jnp.full((n,), 0.25),
        optimizer=sgd(0.05), faults=faults, use_kernel=True)
    with tracing.span("test.run") as record:
        sim.run(jax.random.PRNGKey(0), params, 3)
    if arrives == "tree":
        # Counted at trace time, once for the one traced step body, and
        # with no note: readers take a record's notes for its compiles.
        assert record.counters["sim.update_leafwise"] == 1
        assert all(nt["counter"] in ("compiles", "cache_loads")
                   for nt in record.notes)
    else:
        assert "sim.update_leafwise" not in record.counters


# --------------------------------- tree gradients, reduced leaf by leaf

#: A ragged population: the last two of six rows do not exist.
LEAFWISE_ACTIVE = jnp.array([1.0, 1.0, 1.0, 1.0, 0.0, 0.0])


def _poisoned_problem(poison: bool):
    """The dict problem over six rows whose two inactive rows are inf and
    NaN (``poison``) or zeroed by hand."""
    n = LEAFWISE_ACTIVE.shape[0]
    params, grads_fn, loss_fn = _dict_problem(n)
    fill = jnp.array([0.0, 0.0, 0.0, 0.0, jnp.inf, jnp.nan]) if poison \
        else jnp.zeros((n,))

    def poisoned(p, key, t):
        return jax.tree_util.tree_map(
            lambda g: jnp.where(
                LEAFWISE_ACTIVE.reshape((-1,) + (1,) * (g.ndim - 1)) > 0,
                g, fill.reshape((-1,) + (1,) * (g.ndim - 1))),
            grads_fn(p, key, t))

    return params, poisoned, loss_fn


def _ragged_run(grads_fn, params, loss_fn, opt, use_kernel, flat=None):
    n = LEAFWISE_ACTIVE.shape[0]
    sim = ClientSimulator(
        grads_fn=grads_fn, scheduler=make_scheduler("alg1", n),
        energy=BinaryArrivals([0.7] * n),
        p=LEAFWISE_ACTIVE / jnp.sum(LEAFWISE_ACTIVE), optimizer=opt,
        loss_fn=loss_fn, use_kernel=use_kernel, flat=flat)
    w, h = sim.run(jax.random.PRNGKey(7), params, 8,
                   active_mask=LEAFWISE_ACTIVE)
    return jax.tree_util.tree_map(np.asarray, (w, h))


@pytest.mark.parametrize("opt", [sgd(0.05), adam(0.05)], ids=["sgd", "adam"])
@pytest.mark.parametrize("use_kernel", [False, True],
                         ids=["matvec", "kernel"])
def test_leafwise_step_matches_oracle_and_flat_buffer(opt, use_kernel):
    """A tree-gradient run over a ragged population with inf/NaN in its
    masked rows: the leafwise step matches the per-leaf oracle
    (``flat=False``) and the flat-buffer path (the same gradients
    delivered as one ``(N, P)`` array) to f32 tolerance over eight
    rounds; poisoning the masked rows is bitwise the same as zeroing them
    by hand; ``weight_sum`` is bitwise the flat path's."""
    params, poisoned, loss_fn = _poisoned_problem(True)
    _, zeroed, _ = _poisoned_problem(False)
    w, h = _ragged_run(poisoned, params, loss_fn, opt, use_kernel)
    w0, h0 = _ragged_run(zeroed, params, loss_fn, opt, use_kernel)
    for a, b in zip(jax.tree_util.tree_leaves((w, h)),
                    jax.tree_util.tree_leaves((w0, h0))):
        np.testing.assert_array_equal(a, b)
    assert np.all(np.isfinite(h.loss))
    assert h.participation[:, 4:].sum() == 0
    oracle = _ragged_run(poisoned, params, loss_fn, opt, use_kernel,
                         flat=False)
    flat_buffer = _ragged_run(
        lambda p, k, t: aggregation.ravel_stacked(poisoned(p, k, t)),
        params, loss_fn, opt, use_kernel)
    for w_ref, h_ref in (oracle, flat_buffer):
        np.testing.assert_array_equal(h.weight_sum, h_ref.weight_sum)
        np.testing.assert_array_equal(h.participation, h_ref.participation)
        np.testing.assert_allclose(h.loss, h_ref.loss, rtol=2e-4, atol=1e-5)
        for name in w:
            np.testing.assert_allclose(w[name], w_ref[name],
                                       rtol=2e-4, atol=1e-5)


def test_flat_carry_tolerates_mixed_dtype_grads():
    """Uniform-dtype params with a grads_fn that emits one bf16 leaf:
    the flat carry casts gradients to the params dtype instead of
    crashing (regression: pre-flat per-leaf aggregation accepted this)."""
    n = 4
    params = {"a": jnp.full((3,), 0.5), "b": jnp.full((2, 2), 0.5)}

    def grads_fn(p, key, t):
        g = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x * 0.1, (n,) + x.shape), p)
        return {"a": g["a"], "b": g["b"].astype(jnp.bfloat16)}

    mk = lambda flat: ClientSimulator(
        grads_fn=grads_fn, scheduler=make_scheduler("alg1", n),
        energy=BinaryArrivals([0.5] * n), p=jnp.full((n,), 0.25),
        optimizer=sgd(0.1), flat=flat)
    w_flat, _ = mk(None).run(jax.random.PRNGKey(0), params, 10)
    w_leaf, _ = mk(False).run(jax.random.PRNGKey(0), params, 10)
    for name in params:
        assert w_flat[name].dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(w_flat[name]),
                                   np.asarray(w_leaf[name]),
                                   rtol=2e-2, atol=2e-2)


def test_flat_true_raises_on_mixed_dtype_params():
    params = {"a": jnp.zeros((3,), jnp.float32),
              "b": jnp.zeros((2,), jnp.bfloat16)}
    sim = ClientSimulator(
        grads_fn=lambda p, k, t: jax.tree_util.tree_map(
            lambda x: jnp.zeros((2,) + x.shape, x.dtype), p),
        scheduler=make_scheduler("alg1", 2),
        energy=BinaryArrivals([0.5, 0.5]), p=jnp.array([0.5, 0.5]),
        optimizer=sgd(0.1), flat=True)
    with pytest.raises(ValueError, match="single leaf dtype"):
        sim.run(jax.random.PRNGKey(0), params, 4)
    # flat=None quietly falls back to the per-leaf carry.
    sim.flat = None
    w, _ = sim.run(jax.random.PRNGKey(0), params, 4)
    assert w["a"].dtype == jnp.float32 and w["b"].dtype == jnp.bfloat16


def test_quadratic_flat_vs_legacy_end_to_end():
    """Single-array params (the paper's quadratic problems) through both
    carries and both aggregation backends, full trajectory equality."""
    prob = make_quadratic(jax.random.PRNGKey(0), n_clients=4, dim=8)
    det = DeterministicArrivals.periodic([1, 2, 4, 8], horizon=80)
    runs = {}
    for flat in (False, None):
        for uk in (False, True):
            sim = ClientSimulator(
                grads_fn=lambda p, k, t: prob.all_grads(p),
                scheduler=make_scheduler("alg1", 4), energy=det, p=prob.p,
                optimizer=sgd(0.02), loss_fn=prob.suboptimality,
                use_kernel=uk, flat=flat)
            w, _ = sim.run(jax.random.PRNGKey(5), jnp.zeros(8), 60)
            runs[(flat, uk)] = np.asarray(w)
    base = runs[(False, False)]
    for key, w in runs.items():
        np.testing.assert_allclose(base, w, rtol=1e-4, atol=1e-5,
                                   err_msg=str(key))
