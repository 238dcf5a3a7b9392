"""Within-cell client-axis sharding (DESIGN.md §8): differential suite.

The tentpole guarantee, locked in bit-for-bit: running one cell with its
client axis sharded across devices — per-client component rows,
arrivals/battery state, scheduler rows, ``active_mask`` and the
``(N, P)`` gradient buffer all device-local, the aggregation reduced
across the ``clients`` mesh axis, the server update replicated —
produces *exactly* the numbers of the single-device vmap path, across
all six schedulers × all four arrival families, including ragged
(masked) cells.

``reduction="gather"`` is the bitwise contract (the global gradient
buffer is reassembled in exact row order and every shard replays the
identical unsharded reduction) — the differential oracle every other
mode is held against. The *default* under an active clients axis is
``"psum"`` (DESIGN.md §9): bandwidth-optimal, float32-reassociation
tolerance; ``"fused[_bf16]"`` additionally folds the SGD update into
the local launch, and ``"psum_bf16"`` quantizes the wire. Combined
``(cells, clients)`` meshes must keep the one-trace-per-structure
guarantee of the cell-sharded path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.core import ClientSimulator, make_quadratic, scheduler_names
from repro.core.energy import make_arrivals
from repro.core.scheduling import make_scheduler
from repro.experiments import (
    ExecutionConfig,
    Study,
    make_cell_mesh,
    make_client_mesh,
    make_grid_mesh,
    run_client_sharded,
)
from repro.experiments import placement
from repro.optim import sgd

clientshard = pytest.mark.clientshard
multidevice = pytest.mark.multidevice

N_CAP, DIM = 8, 5

ARRIVALS = ("periodic", "binary", "uniform", "day_night")

SCHEDULER_ARRIVALS = [(s, a) for s in scheduler_names() for a in ARRIVALS]


@pytest.fixture(scope="module")
def master():
    return make_quadratic(jax.random.PRNGKey(2), n_clients=N_CAP, dim=DIM,
                          hetero=1.0)


@pytest.fixture(scope="module")
def loss_fn(master):
    # Elementwise + one sum: bit-stable under vmap (see test_ragged.py).
    w_star = master.w_star
    return lambda w: jnp.sum((w - w_star) ** 2)


@pytest.fixture(scope="module")
def sim(master, loss_fn):
    return ClientSimulator(grads_fn=lambda w, k, t: master.all_grads(w),
                           p=master.p, optimizer=sgd(0.02), loss_fn=loss_fn)


@pytest.fixture(scope="module")
def params0():
    return jnp.full((DIM,), 4.0)


def assert_cells_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.history.loss),
                                  np.asarray(b.history.loss))
    np.testing.assert_array_equal(np.asarray(a.history.participation),
                                  np.asarray(b.history.participation))
    np.testing.assert_array_equal(np.asarray(a.history.weight_sum),
                                  np.asarray(b.history.weight_sum))
    np.testing.assert_array_equal(np.asarray(a.params), np.asarray(b.params))


# --------------------------------------------------------- mesh factories

def test_make_client_mesh_axis_name():
    mesh = make_client_mesh()
    assert mesh.axis_names == (placement.CLIENT_AXIS,)
    assert mesh.size == jax.device_count()


@multidevice
def test_make_grid_mesh_shape():
    mesh = make_grid_mesh(2, jax.device_count() // 2)
    assert mesh.axis_names == (placement.CELL_AXIS, placement.CLIENT_AXIS)
    assert mesh.shape[placement.CELL_AXIS] == 2


def test_mesh_axes_resolution():
    assert placement._mesh_axes(make_cell_mesh(1)) == ("cells", None)
    assert placement._mesh_axes(make_client_mesh(1)) == (None, "clients")
    assert placement._mesh_axes(make_grid_mesh(1, 1)) == ("cells", "clients")
    bad = jax.sharding.Mesh(
        np.array(jax.devices()[:1]).reshape(1, 1), ("clients", "cells"))
    with pytest.raises(ValueError, match="clients"):
        placement._mesh_axes(bad)


def test_client_leaf_specs_shape_rule():
    from jax.sharding import PartitionSpec as P

    tree = {"rows": jnp.zeros((N_CAP, 3)), "scalar": jnp.zeros(()),
            "vec": jnp.zeros((3,))}
    specs = placement.client_leaf_specs(tree, N_CAP, client_axis="clients")
    by_leaf = dict(zip(sorted(tree), specs))
    assert by_leaf["rows"] == P("clients")
    assert by_leaf["scalar"] == P()
    assert by_leaf["vec"] == P()
    # grid layout: leading cell axis, client axis on dim 1
    specs = placement.client_leaf_specs(
        {"rows": jnp.zeros((4, N_CAP, 3)), "scalar": jnp.zeros((4,))},
        N_CAP, client_axis="clients", cell_axis="cells", lead=1)
    assert specs == [P("cells", "clients"), P("cells")]


# ----------------------------------------------- bitwise differential suite

@clientshard
@multidevice
@pytest.mark.parametrize("scheduler,arrivals", SCHEDULER_ARRIVALS)
def test_client_sharded_matches_vmap_bitwise(sim, params0, scheduler,
                                             arrivals):
    """Acceptance: the 8-device client-sharded run of every scheduler ×
    arrival-family cell — including a ragged (masked) population —
    equals the single-device vmap run bit-for-bit."""
    num_steps, seeds, pops = 15, 2, (5, 8)
    study = Study("cs", num_steps=num_steps, axes={
        "scheduler": scheduler, "arrivals": arrivals,
        "n_clients": list(pops), "seeds": seeds})
    plain = study.run(sim=sim, params0=params0)
    sharded = study.run(sim=sim, params0=params0,
                        config=ExecutionConfig(mesh=make_client_mesh(),
                                               client_reduction="gather"))
    for n in pops:
        name = f"{scheduler}_{arrivals}_n{n}"
        assert sharded[name].history.participation.shape == \
            (seeds, num_steps, n)
        assert_cells_equal(plain[name], sharded[name])


@clientshard
def test_all_six_schedulers_are_covered():
    assert sorted({s for s, _ in SCHEDULER_ARRIVALS}) == scheduler_names()
    assert sorted({a for _, a in SCHEDULER_ARRIVALS}) == sorted(ARRIVALS)


@clientshard
@multidevice
def test_single_cell_run_client_sharded_bitwise(sim, params0):
    """run_client_sharded (the single-population entry point) ==
    ClientSimulator.run, bit-for-bit, history and final params."""
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 21)
    key = jax.random.PRNGKey(0)
    pu, hu = sim.run(key, params0, 20, scheduler=scheduler, energy=energy)
    ps, hs = run_client_sharded(sim, key, params0, 20, scheduler=scheduler,
                                energy=energy, mesh=make_client_mesh(),
                                reduction="gather")
    np.testing.assert_array_equal(np.asarray(pu), np.asarray(ps))
    np.testing.assert_array_equal(np.asarray(hu.loss), np.asarray(hs.loss))
    np.testing.assert_array_equal(np.asarray(hu.participation),
                                  np.asarray(hs.participation))
    np.testing.assert_array_equal(np.asarray(hu.weight_sum),
                                  np.asarray(hs.weight_sum))


def _tree_sim(master, *, aware: bool) -> ClientSimulator:
    """The quadratic with params ``{"a": (2,), "b": (DIM-2,)}``: client
    gradients arrive as a two-leaf tree. ``aware`` makes the grads_fn
    client-aware, returning the selected rows of the full call."""
    w_star = master.w_star

    def joined(w):
        return jnp.concatenate([w["a"], w["b"]])

    def grads_fn(w, k, t, clients=None):
        g = master.all_grads(joined(w))
        if clients is not None:
            g = g[clients]
        return {"a": g[:, :2], "b": g[:, 2:]}

    return ClientSimulator(
        grads_fn=grads_fn if aware else (lambda w, k, t: grads_fn(w, k, t)),
        p=master.p, optimizer=sgd(0.02),
        loss_fn=lambda w: jnp.sum((joined(w) - w_star) ** 2))


@clientshard
@multidevice
@pytest.mark.parametrize("reduction,aware", [
    ("gather", False), ("gather", True), ("psum", False), ("fused", True)])
def test_tree_gradients_client_sharded(master, reduction, aware):
    """Gradients that arrive as a tree: ``gather`` reassembles the rows
    of every leaf and replays the unsharded leafwise reduction, bitwise
    the single-device run; ``psum``/``fused`` reduce the flat rows, to
    f32 tolerance."""
    sim = _tree_sim(master, aware=aware)
    params0 = {"a": jnp.full((2,), 4.0), "b": jnp.full((DIM - 2,), 4.0)}
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 16)
    key = jax.random.PRNGKey(3)
    pu, hu = sim.run(key, params0, 15, scheduler=scheduler, energy=energy)
    with tracing.span("test.sharded") as record:
        ps, hs = run_client_sharded(sim, key, params0, 15,
                                    scheduler=scheduler, energy=energy,
                                    mesh=make_client_mesh(),
                                    reduction=reduction)
    assert ("sim.update_leafwise" in record.counters) == (
        reduction == "gather")
    np.testing.assert_array_equal(np.asarray(hu.participation),
                                  np.asarray(hs.participation))
    for a, b in zip(jax.tree_util.tree_leaves((pu, hu.loss, hu.weight_sum)),
                    jax.tree_util.tree_leaves((ps, hs.loss, hs.weight_sum))):
        if reduction == "gather":
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        else:
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)


@clientshard
@multidevice
def test_large_population_cell_bitwise():
    """Acceptance criterion: a single N=4096-client cell client-sharded
    on 8 host devices is bit-for-bit the unsharded vmap run."""
    if jax.device_count() < 8 or 4096 % jax.device_count() != 0:
        pytest.skip("needs a device count dividing 4096 (CI forces 8)")
    n, dim, steps = 4096, 8, 6
    prob = make_quadratic(jax.random.PRNGKey(7), n_clients=n, dim=dim,
                          hetero=1.0)
    w_star = prob.w_star
    sim = ClientSimulator(grads_fn=lambda w, k, t: prob.all_grads(w),
                          p=prob.p, optimizer=sgd(0.01),
                          loss_fn=lambda w: jnp.sum((w - w_star) ** 2))
    scheduler = make_scheduler("alg2", n)
    energy = make_arrivals("binary", n, steps + 1)
    key = jax.random.PRNGKey(1)
    params0 = jnp.full((dim,), 2.0)
    pu, hu = sim.run(key, params0, steps, scheduler=scheduler, energy=energy)
    ps, hs = run_client_sharded(sim, key, params0, steps, scheduler=scheduler,
                                energy=energy, mesh=make_client_mesh(),
                                reduction="gather")
    np.testing.assert_array_equal(np.asarray(pu), np.asarray(ps))
    np.testing.assert_array_equal(np.asarray(hu.loss), np.asarray(hs.loss))
    np.testing.assert_array_equal(np.asarray(hu.participation),
                                  np.asarray(hs.participation))


@clientshard
@multidevice
def test_eval_chunked_run_client_sharded(sim, params0, loss_fn):
    """The chunked in-loop eval path runs client-sharded too."""
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 21)
    key = jax.random.PRNGKey(3)
    pu, hu, eu = sim.run(key, params0, 20, scheduler=scheduler, energy=energy,
                         eval_fn=loss_fn, eval_every=10)
    ps, hs, es = run_client_sharded(sim, key, params0, 20,
                                    scheduler=scheduler, energy=energy,
                                    mesh=make_client_mesh(),
                                    eval_fn=loss_fn, eval_every=10,
                                    reduction="gather")
    assert es.shape == (2,)
    np.testing.assert_array_equal(np.asarray(eu), np.asarray(es))
    np.testing.assert_array_equal(np.asarray(hu.loss), np.asarray(hs.loss))


# --------------------------------------------------- combined cells×clients

@clientshard
@multidevice
def test_combined_mesh_traces_once_per_structure(sim, params0):
    """cells×clients mesh: one _run_group_sharded trace per component
    structure, zero on repeat — exactly the cells-only guarantee."""
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices for a 2x2 grid mesh")
    mesh = make_grid_mesh(2, 2)
    study = Study("cs2", num_steps=13, axes={
        "scheduler": ["alg1", "alg2"], "arrivals": ["binary", "uniform"],
        "n_clients": N_CAP, "seeds": 3})
    cfg = ExecutionConfig(mesh=mesh)
    before = placement._run_group_sharded._cache_size()
    plain = study.run(sim=sim, params0=params0)
    sharded = study.run(sim=sim, params0=params0, config=cfg)
    assert placement._run_group_sharded._cache_size() - before == 4
    study.run(sim=sim, params0=params0, config=cfg)
    assert placement._run_group_sharded._cache_size() - before == 4
    for name in plain:
        np.testing.assert_allclose(np.asarray(plain[name].history.loss),
                                   np.asarray(sharded[name].history.loss),
                                   rtol=2e-4, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(plain[name].history.participation),
            np.asarray(sharded[name].history.participation))


@clientshard
@multidevice
def test_combined_mesh_ragged_grid(sim, params0):
    """Ragged populations survive the combined mesh: masked cells over
    cells×clients sharding match the vmap path (exact participation)."""
    if jax.device_count() < 4:
        pytest.skip("needs >= 4 devices for a 2x2 grid mesh")
    study = Study("cs3", num_steps=12, axes={
        "scheduler": "alg2", "arrivals": "binary",
        "n_clients": [4, 8], "seeds": 2})
    plain = study.run(sim=sim, params0=params0)
    sharded = study.run(sim=sim, params0=params0,
                        config=ExecutionConfig(mesh=make_grid_mesh(2, 2)))
    for name in plain:
        np.testing.assert_allclose(np.asarray(plain[name].history.loss),
                                   np.asarray(sharded[name].history.loss),
                                   rtol=2e-4, atol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(plain[name].history.participation),
            np.asarray(sharded[name].history.participation))


# ----------------------------------------------------- psum / kernel modes

@clientshard
@multidevice
def test_psum_reduction_matches_gather(sim, params0):
    """reduction='psum' (local partial matvec + psum) agrees with the
    bitwise gather mode to f32 reassociation tolerance; participation
    (RNG + scheduling, no reduction involved) stays exact."""
    study = Study("cs", num_steps=15, axes={
        "scheduler": "alg2", "arrivals": "binary",
        "n_clients": [5, 8], "seeds": 2})
    gather = study.run(sim=sim, params0=params0,
                       config=ExecutionConfig(mesh=make_client_mesh(),
                                              client_reduction="gather"))
    psum = study.run(sim=sim, params0=params0,
                     config=ExecutionConfig(mesh=make_client_mesh(),
                                            client_reduction="psum"))
    for name in gather:
        np.testing.assert_allclose(np.asarray(gather[name].history.loss),
                                   np.asarray(psum[name].history.loss),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(gather[name].history.participation),
            np.asarray(psum[name].history.participation))
        np.testing.assert_allclose(np.asarray(gather[name].history.weight_sum),
                                   np.asarray(psum[name].history.weight_sum),
                                   rtol=1e-6, atol=1e-6)


@clientshard
@multidevice
def test_kernel_path_client_sharded(master, params0, loss_fn):
    """use_kernel=True routes the sharded-operand Pallas path (local
    tiled kernel + psum) — agrees with the jnp path."""
    kw = dict(grads_fn=lambda w, k, t: master.all_grads(w), p=master.p,
              optimizer=sgd(0.02), loss_fn=loss_fn)
    study = Study("cs", num_steps=10, axes={
        "scheduler": "alg2", "arrivals": "binary",
        "n_clients": [5, 8], "seeds": 2})
    cfg = ExecutionConfig(mesh=make_client_mesh(), client_reduction="psum")
    plain = study.run(sim=ClientSimulator(**kw), params0=params0)
    kern = study.run(sim=ClientSimulator(use_kernel=True, **kw),
                     params0=params0, config=cfg)
    for name in plain:
        np.testing.assert_allclose(np.asarray(plain[name].history.loss),
                                   np.asarray(kern[name].history.loss),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_array_equal(
            np.asarray(plain[name].history.participation),
            np.asarray(kern[name].history.participation))


@clientshard
def test_default_client_reduction_is_psum():
    """The production default under a clients axis is psum (DESIGN.md
    §9 decision table); gather remains opt-in as the bitwise oracle."""
    assert ExecutionConfig().client_reduction == "psum"
    import inspect

    sig = inspect.signature(run_client_sharded)
    assert sig.parameters["reduction"].default == "psum"


@clientshard
@multidevice
@pytest.mark.parametrize("reduction", ["fused", "fused_bf16", "psum_bf16"])
def test_fused_and_wire_modes_match_gather(sim, params0, reduction):
    """The fused reduce-and-update modes and the bf16-wire psum agree
    with the bitwise gather oracle to their documented tolerances
    (DESIGN.md §9); participation (RNG + scheduling, no reduction
    involved) stays exact."""
    study = Study("cs", num_steps=15, axes={
        "scheduler": "alg2", "arrivals": "binary",
        "n_clients": [5, 8], "seeds": 2})
    gather = study.run(sim=sim, params0=params0,
                       config=ExecutionConfig(mesh=make_client_mesh(),
                                              client_reduction="gather"))
    other = study.run(sim=sim, params0=params0,
                      config=ExecutionConfig(mesh=make_client_mesh(),
                                             client_reduction=reduction))
    # bf16 wire: one quantization of the (P,) partial per shard per
    # step, f32 accumulation on both sides — bf16-relative tolerance.
    rtol, atol = (1e-5, 1e-6) if "bf16" not in reduction else (2e-2, 1e-2)
    for name in gather:
        np.testing.assert_allclose(np.asarray(gather[name].history.loss),
                                   np.asarray(other[name].history.loss),
                                   rtol=rtol, atol=atol)
        np.testing.assert_array_equal(
            np.asarray(gather[name].history.participation),
            np.asarray(other[name].history.participation))


@clientshard
@multidevice
def test_fused_requires_sgd(master, params0, loss_fn):
    """reduction='fused' with a stateful optimizer is a clear
    trace-time error, never silently-wrong numerics."""
    from repro.optim import adam

    sim = ClientSimulator(grads_fn=lambda w, k, t: master.all_grads(w),
                          p=master.p, optimizer=adam(1e-2), loss_fn=loss_fn)
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 6)
    with pytest.raises(ValueError, match="sgd"):
        run_client_sharded(sim, jax.random.PRNGKey(0), params0, 5,
                           scheduler=scheduler, energy=energy,
                           mesh=make_client_mesh(), reduction="fused")


# --------------------------------------------------- client-aware grads_fn

@clientshard
@multidevice
def test_client_aware_grads_fn_shards_compute(master, params0, loss_fn):
    """A grads_fn accepting ``clients=`` computes only its shard's rows
    (the compute-sharding protocol) and agrees with the full-compute
    fallback to f32 tolerance; scheduling/participation stays exact."""
    def grads_cs(w, k, t, clients=None):
        if clients is None:
            return master.all_grads(w)
        return jnp.einsum("nij,j->ni", master.a[clients], w) \
            - master.b[clients]

    sim_full = ClientSimulator(grads_fn=lambda w, k, t: master.all_grads(w),
                               p=master.p, optimizer=sgd(0.02),
                               loss_fn=loss_fn)
    sim_aware = ClientSimulator(grads_fn=grads_cs, p=master.p,
                                optimizer=sgd(0.02), loss_fn=loss_fn)
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 16)
    key = jax.random.PRNGKey(5)
    pu, hu = sim_full.run(key, params0, 15, scheduler=scheduler,
                          energy=energy)
    ps, hs = run_client_sharded(sim_aware, key, params0, 15,
                                scheduler=scheduler, energy=energy,
                                mesh=make_client_mesh())
    np.testing.assert_allclose(np.asarray(pu), np.asarray(ps),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(hu.participation),
                                  np.asarray(hs.participation))


# ------------------------------------------------------------- validation

@clientshard
@multidevice
def test_capacity_must_divide_client_shards(sim, params0):
    if jax.device_count() < 3:
        pytest.skip("needs >= 3 devices")
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 6)
    with pytest.raises(ValueError, match="divide"):
        run_client_sharded(sim, jax.random.PRNGKey(0), params0, 5,
                           scheduler=scheduler, energy=energy,
                           mesh=make_client_mesh(3))


@clientshard
def test_run_client_sharded_rejects_cells_mesh(sim, params0):
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 6)
    with pytest.raises(ValueError, match="clients"):
        run_client_sharded(sim, jax.random.PRNGKey(0), params0, 5,
                           scheduler=scheduler, energy=energy,
                           mesh=make_cell_mesh(1))


@clientshard
@multidevice
def test_legacy_per_leaf_path_rejected_under_sharding(master, params0,
                                                     loss_fn):
    """flat=False (per-leaf carry) cannot run client-sharded — a clear
    trace-time error, not silent wrong numerics."""
    sim = ClientSimulator(grads_fn=lambda w, k, t: master.all_grads(w),
                          p=master.p, optimizer=sgd(0.02), loss_fn=loss_fn,
                          flat=False)
    scheduler = make_scheduler("alg2", N_CAP)
    energy = make_arrivals("binary", N_CAP, 6)
    with pytest.raises(ValueError, match="flat"):
        run_client_sharded(sim, jax.random.PRNGKey(0), params0, 5,
                           scheduler=scheduler, energy=energy,
                           mesh=make_client_mesh())
