"""Fused reduce-and-update path (DESIGN.md §9).

Locks the tentpole contracts of the fused Pallas server step:

* kernel ≡ interpret-mode reference (``masked_scaled_aggregate_update_ref``)
  across shapes, with and without mask/params, f32 and bf16 inputs
  (f32 in-kernel accumulation);
* mask-poisoned rows (inf/NaN) contribute **exact zeros**;
* the reduction grammar ``gather | psum[_bf16] | fused[_bf16]``;
* bf16-on-the-wire partial sums accumulate in f32 (quantize once per
  shard, never accumulate in bf16);
* the sharded fused step is a **single Pallas launch** per step
  (jaxpr-walk launch count);
* ``run_carry`` donates the flat carry: no warnings, the input buffers
  are consumed, and the donated chunked run resumes bitwise.
"""

import warnings

import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClientSimulator, make_quadratic, make_scheduler
from repro.core.aggregation import (
    _cross_shard_sum,
    fused_flat_sgd_update,
    parse_reduction,
)
from repro.core.energy import BinaryArrivals, make_arrivals
from repro.experiments import make_client_mesh, run_client_sharded
from repro.kernels.aggregate import ops as agg_ops
from repro.kernels.aggregate.ref import masked_scaled_aggregate_update_ref
from repro.optim import adam, sgd

multidevice = pytest.mark.multidevice


# ------------------------------------------------------- kernel vs oracle

SHAPES = [(1, 1), (3, 129), (8, 300), (17, 2048), (64, 2049)]


@pytest.mark.parametrize("n,p", SHAPES)
@pytest.mark.parametrize("with_params", [False, True], ids=["delta", "update"])
@pytest.mark.parametrize("with_mask", [False, True], ids=["dense", "masked"])
def test_fused_kernel_matches_ref_f32(n, p, with_params, with_mask):
    rng = np.random.default_rng(n * 1000 + p)
    g = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    w = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    params = jnp.asarray(rng.normal(size=(p,)), jnp.float32) \
        if with_params else None
    mask = jnp.asarray(rng.integers(0, 2, size=(n,)), jnp.float32) \
        if with_mask else None
    eta = 0.07
    out = agg_ops.masked_scaled_aggregate_update(g, w, eta, params, mask)
    ref = masked_scaled_aggregate_update_ref(g, w, eta, params, mask)
    assert out.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_fused_kernel_bf16_inputs_f32_accumulation():
    """bf16 gradient rows, f32 params: the kernel upcasts per tile and
    accumulates f32 — the result matches the f32 oracle of the *same
    bf16-rounded inputs* to f32 tolerance, far tighter than any bf16
    accumulation could achieve at N=512."""
    rng = np.random.default_rng(0)
    n, p = 512, 700
    g = jnp.asarray(rng.normal(size=(n, p)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    params = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
    out = agg_ops.masked_scaled_aggregate_update(g, w, 0.01, params)
    assert out.dtype == jnp.float32
    ref = masked_scaled_aggregate_update_ref(g, w, 0.01, params)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # delta mode defaults to the f32 accumulation dtype, not bf16
    delta = agg_ops.masked_scaled_aggregate_update(g, w, 0.01, None)
    assert delta.dtype == jnp.float32


@pytest.mark.parametrize("use_kernel", [False, True], ids=["jnp", "kernel"])
def test_fused_update_poisoned_masked_rows_exact_zero(use_kernel):
    """Acceptance: inf/NaN gradient rows behind mask=0 contribute exact
    zeros through the fused update — bitwise equal to zeroing the rows
    by hand, all the way through fused_flat_sgd_update."""
    rng = np.random.default_rng(3)
    n, p = 16, 260
    g = jnp.asarray(rng.normal(size=(n, p)), jnp.float32)
    poisoned = g.at[2].set(jnp.inf).at[9].set(jnp.nan).at[11].set(-jnp.inf)
    mask = jnp.ones((n,), jnp.float32).at[2].set(0).at[9].set(0).at[11].set(0)
    w = jnp.asarray(rng.uniform(size=(n,)), jnp.float32)
    params = jnp.asarray(rng.normal(size=(p,)), jnp.float32)
    opt = sgd(0.05)
    st = opt.init(params)
    clean = g * mask[:, None]
    out_p, _, _ = fused_flat_sgd_update(poisoned, w, params, st, opt,
                                        mask=mask, use_kernel=use_kernel)
    out_c, _, _ = fused_flat_sgd_update(clean, w, params, st, opt,
                                        mask=mask, use_kernel=use_kernel)
    assert bool(jnp.all(jnp.isfinite(out_p)))
    np.testing.assert_array_equal(np.asarray(out_p), np.asarray(out_c))


def test_fused_update_rejects_untagged_optimizer():
    g = jnp.ones((2, 4))
    w = jnp.ones((2,))
    params = jnp.zeros((4,))
    opt = adam(0.1)
    with pytest.raises(ValueError, match="sgd"):
        fused_flat_sgd_update(g, w, params, opt.init(params), opt)


def test_sgd_is_tagged_for_fusion_and_wrappers_are_not():
    from repro.optim import chain_clip, momentum

    assert sgd(0.1).kind == "sgd"
    assert sgd(0.1).hyper == 0.1
    assert momentum(0.1).kind == ""
    assert adam(0.1).kind == ""
    assert chain_clip(sgd(0.1), 1.0).kind == ""


def test_fused_update_schedule_lr():
    """A schedule lr is resolved at the carried step, matching the
    unfused sgd().update numerics exactly."""
    sched = lambda step: 0.1 / (1.0 + step.astype(jnp.float32))
    opt = sgd(sched)
    rng = np.random.default_rng(5)
    g = jnp.asarray(rng.normal(size=(3, 20)), jnp.float32)
    w = jnp.asarray(rng.uniform(size=(3,)), jnp.float32)
    params = jnp.asarray(rng.normal(size=(20,)), jnp.float32)
    st = opt.init(params)
    st = st._replace(step=jnp.asarray(7, jnp.int32))
    fused_p, fused_st, _ = fused_flat_sgd_update(g, w, params, st, opt)
    agg = w @ g
    updates, ref_st = opt.update(agg, st)
    np.testing.assert_array_equal(np.asarray(fused_p),
                                  np.asarray(params + updates))
    assert int(fused_st.step) == int(ref_st.step) == 8


# ------------------------------------------------------ reduction grammar

def test_parse_reduction_grammar():
    assert parse_reduction("gather") == ("gather", None)
    assert parse_reduction("psum") == ("psum", None)
    assert parse_reduction("fused") == ("fused", None)
    assert parse_reduction("psum_bf16") == ("psum", jnp.bfloat16)
    assert parse_reduction("fused_bf16") == ("fused", jnp.bfloat16)


@pytest.mark.parametrize("bad", ["gather_bf16", "psum_f16", "allgather",
                                 "fused_f32", ""])
def test_parse_reduction_rejects(bad):
    with pytest.raises(ValueError):
        parse_reduction(bad)


def test_client_sharding_context_validates_reduction():
    from repro.core.energy import client_sharding

    with pytest.raises(ValueError):
        with client_sharding("clients", 2, "gather_bf16"):
            pass
    with client_sharding("clients", 2, "fused_bf16"):
        pass


# ------------------------------------------------------- bf16 wire semantics

@multidevice
def test_cross_shard_sum_bf16_wire_f32_accumulation():
    """The bf16 wire quantizes each shard's partial ONCE and accumulates
    the gathered partials in f32 — bitwise equal to the explicit
    quantize-then-f32-sum, not to a bf16-accumulated psum."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = make_client_mesh()
    shards = mesh.size
    rng = np.random.default_rng(11)
    partials = jnp.asarray(rng.normal(size=(shards, 64)), jnp.float32)

    fn = shard_map(
        lambda x: _cross_shard_sum(x[0], "clients", jnp.bfloat16)[None],
        mesh=mesh, in_specs=P("clients"), out_specs=P("clients"),
        check_vma=False)
    out = np.asarray(fn(partials)[0])
    expected = np.sum(np.asarray(partials.astype(jnp.bfloat16)
                                 .astype(jnp.float32)), axis=0)
    np.testing.assert_array_equal(out, expected)
    exact = np.sum(np.asarray(partials), axis=0)
    np.testing.assert_allclose(out, exact, rtol=2e-2, atol=1e-2)


# --------------------------------------------------- single-launch contract

def _count_pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(
                        x, (jex_core.Jaxpr, jex_core.ClosedJaxpr))):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    n += _count_pallas_calls(sub.jaxpr)
                elif isinstance(sub, jex_core.Jaxpr):
                    n += _count_pallas_calls(sub)
    return n


@multidevice
def test_sharded_fused_step_is_single_pallas_launch():
    """Acceptance: the client-sharded fused hot loop contains exactly
    ONE pallas_call in its step program — the fused reduce-and-update
    launch; the parameter update is not a second kernel."""
    n, dim, steps = 8, 5, 4
    prob = make_quadratic(jax.random.PRNGKey(0), n_clients=n, dim=dim)
    sim = ClientSimulator(grads_fn=lambda w, k, t: prob.all_grads(w),
                          p=prob.p, optimizer=sgd(0.02), use_kernel=True)
    scheduler = make_scheduler("alg2", n)
    energy = make_arrivals("binary", n, steps + 1)
    params0 = jnp.full((dim,), 2.0)

    jaxpr = jax.make_jaxpr(
        lambda k, p0: run_client_sharded(
            sim, k, p0, steps, scheduler=scheduler, energy=energy,
            mesh=make_client_mesh(), reduction="fused"))(
        jax.random.PRNGKey(1), params0)
    # The scan body traces once, so the whole program holds exactly the
    # per-step launch count.
    assert _count_pallas_calls(jaxpr.jaxpr) == 1


# ------------------------------------------------------------- donation

def _donation_sim(n):
    prob = make_quadratic(jax.random.PRNGKey(4), n_clients=n, dim=6)
    sim = ClientSimulator(grads_fn=lambda w, k, t: prob.all_grads(w),
                          p=prob.p, optimizer=sgd(0.03),
                          scheduler=make_scheduler("alg1", n),
                          energy=BinaryArrivals([0.6] * n))
    return sim, jnp.full((6,), 3.0)


def test_run_carry_donates_flat_buffers_silently():
    """Top-level run_carry consumes the input carry's buffers (donation
    took effect) without emitting any donation warnings."""
    n = 4
    sim, params0 = _donation_sim(n)
    spec = sim.flat_spec(params0)
    carry = sim.init(jax.random.PRNGKey(0), params0, spec=spec)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        carry2, hist = sim.run_carry(carry, 5, spec=spec)
    assert carry.params.is_deleted()
    assert not carry2.params.is_deleted()
    assert hist.loss.shape == (5,)
    # the caller's params0 was copied at init, never donated
    assert not params0.is_deleted()
    np.asarray(params0)


def test_run_carry_donation_opt_out():
    n = 4
    sim, params0 = _donation_sim(n)
    spec = sim.flat_spec(params0)
    carry = sim.init(jax.random.PRNGKey(0), params0, spec=spec)
    carry2, _ = sim.run_carry(carry, 5, spec=spec, donate=False)
    assert not carry.params.is_deleted()
    np.asarray(carry.params)


def test_donated_chunked_run_carry_resumes_bitwise():
    """Two donated 10-step run_carry chunks == one 20-step run, bitwise
    — donation aliases buffers without perturbing the step stream."""
    n = 4
    sim, params0 = _donation_sim(n)
    spec = sim.flat_spec(params0)
    carry = sim.init(jax.random.PRNGKey(7), params0, spec=spec)
    c1, h1 = sim.run_carry(carry, 10, spec=spec)
    c2, h2 = sim.run_carry(c1, 10, spec=spec)

    whole = sim.init(jax.random.PRNGKey(7), params0, spec=spec)
    cw, hw = sim.run_carry(whole, 20, spec=spec)
    np.testing.assert_array_equal(np.asarray(cw.params),
                                  np.asarray(c2.params))
    np.testing.assert_array_equal(
        np.asarray(hw.weight_sum),
        np.concatenate([np.asarray(h1.weight_sum),
                        np.asarray(h2.weight_sum)]))


# ----------------------------------------------- SPMD flat train step fused

def test_build_energy_train_step_flat_sgd_routes_fused(monkeypatch):
    """flat=True + tagged sgd() routes through fused_flat_sgd_update and
    matches the unfused flat step bitwise."""
    from repro.core import aggregation as agg_mod
    from repro.core.trainer import build_energy_train_step

    n_clients, dim, bsz = 4, 6, 8
    rng = np.random.default_rng(9)
    w_true = jnp.asarray(rng.normal(size=(dim,)), jnp.float32)

    def per_example_loss(params, batch):
        pred = batch["x"] @ params["w"]
        return (pred - batch["y"]) ** 2

    batch = {
        "x": jnp.asarray(rng.normal(size=(bsz, dim)), jnp.float32),
        "y": jnp.asarray(rng.normal(size=(bsz,)), jnp.float32),
        "client_ids": jnp.repeat(jnp.arange(n_clients, dtype=jnp.int32),
                                 bsz // n_clients),
    }
    params = {"w": jnp.zeros((dim,), jnp.float32) + w_true * 0.1}
    mask = jnp.ones((n_clients,), jnp.float32)
    scale = jnp.ones((n_clients,), jnp.float32)

    calls = []
    real = agg_mod.fused_flat_sgd_update

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(agg_mod, "fused_flat_sgd_update", counting)
    init, step = build_energy_train_step(
        per_example_loss_fn=per_example_loss, optimizer=sgd(0.1),
        n_clients=n_clients, flat=True)
    st1, m1 = step(init(params), batch, mask, scale)
    assert calls, "flat sgd step did not route through the fused update"

    init_a, step_a = build_energy_train_step(
        per_example_loss_fn=per_example_loss, optimizer=adam(0.1),
        n_clients=n_clients, flat=True)
    st2, m2 = step_a(init_a(params), batch, mask, scale)
    np.testing.assert_array_equal(np.asarray(m1["weight_sum"]),
                                  np.asarray(m2["weight_sum"]))
    assert st1.params["w"].shape == (dim,)
