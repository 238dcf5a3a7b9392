"""Placement layer: device-sharded grid execution (DESIGN.md §5, §8).

:func:`repro.experiments.run_grid` batches a structure-group's cells as
``vmap(scenarios) ∘ vmap(seeds)`` on one device. This module places the
same computation across a device mesh instead, along two composable
axes:

**Cell axis** (``"cells"``, DESIGN.md §5) — across-cell parallelism:

1. the (scenario S × seed R) cell block is **flattened** into one cell
   axis C = S·R (scheduler/energy leaves repeated over seeds, PRNG keys
   tiled over scenarios),
2. C is **padded** to a device-divisible count by repeating cell 0 — a
   valid cell, so the padded lanes run real arithmetic instead of
   producing NaNs — and the pad is sliced off before results are
   reshaped back to (S, R, ...),
3. the block executes under ``shard_map``: cells sharded along the
   cell axis, ``params0`` replicated, each device running the same
   jitted ``vmap(ClientSimulator.run)`` over its local cells.

**Client axis** (``"clients"``, DESIGN.md §8) — within-cell parallelism
for populations one device cannot hold: every per-client operand — the
component leaves whose leading (post-cell) dimension is the population
capacity, the ``active_mask`` / ``p`` ragged operands, the scheduler and
energy *state*, and the ``(N, P)`` gradient buffer — is sharded over the
client axis, while params / optimizer state stay **replicated** (every
shard applies the identical server update, so no parameter broadcast is
ever needed). The per-step reduction crosses the axis once: by default
an ``all_gather`` of the gradient rows followed by the *identical*
unsharded reduction on every shard (bit-for-bit the single-device
numbers), or — ``reduction="psum"`` — one local matvec/kernel launch
plus a ``(P,)`` psum (bandwidth-optimal, f32-reassociation tolerance).
Per-client RNG folds in *global* client indices
(:func:`repro.core.energy.client_sharding`), so shard-local rows draw
exactly the unsharded run's bits.

The two axes compose: ``make_grid_mesh(cells=4, clients=2)`` runs 4-way
cell sharding with each cell's population split over 2 devices.

Single-device callers never enter this module — ``run_grid`` without a
``mesh`` (or with a 1-device mesh) takes the pure-vmap path bit-for-bit
unchanged. CPU CI exercises the sharded paths via
``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(``tests/conftest.py``).

**Multi-host meshes** (DESIGN.md §13): every factory here builds from
*global* devices, so under an initialized ``jax.distributed`` runtime
(:mod:`repro.launch.distributed`) the same meshes span processes —
:func:`make_client_mesh` puts the client axis across hosts (the ROADMAP
mapping: the only per-step collective is the ``(P,)``-sized reduction),
:func:`make_multihost_mesh` pins the cell axis across processes with
clients process-local. Dispatch stays the same ``shard_map`` programs;
the only multi-process difference is at the host boundary — inputs are
lifted to replicated global ``jax.Array``s (every process holds the
full host value, so lifting moves no data) and results are gathered
back to every host as numpy (:func:`run_group_sharded` /
:func:`run_client_sharded` do both automatically when the mesh spans
processes). Gather mode keeps its bitwise contract across hosts;
psum keeps f32-reassociation tolerance.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec

from repro.core.energy import client_sharding
from repro.core.scheduling import shard_scheduler
from repro.core.trainer import SimHistory

#: Default mesh-axis name for the flattened (scenario × seed) cell axis.
CELL_AXIS = "cells"

#: Mesh-axis name for within-cell client sharding. Unlike the cell axis
#: (any single-axis name works, for back-compat), the client axis is
#: recognized *by this name*.
CLIENT_AXIS = "clients"


def device_topology(devices=None) -> str:
    """``"N global devices across K processes"`` — the phrase every
    mesh-shape error uses, so multi-process failures never conflate
    local and global device counts."""
    devices = jax.devices() if devices is None else list(np.ravel(devices))
    procs = {d.process_index for d in devices}
    return (f"{len(devices)} global device(s) across "
            f"{max(len(procs), 1)} process(es)")


def mesh_process_count(mesh: Mesh) -> int:
    """Number of distinct processes the mesh's devices live on — > 1
    means the mesh spans hosts and dispatch must go through the
    global-array boundary (DESIGN.md §13)."""
    return len({d.process_index for d in mesh.devices.flat})


def _device_slice(n_devices: int | None, devices=None):
    """The first ``n_devices`` of ``devices`` (default: all *global*
    devices). ``devices=`` is the explicit multi-host escape hatch —
    pass any iterable of jax devices to pin a layout by hand."""
    devices = list(jax.devices()) if devices is None \
        else list(np.ravel(devices))
    if n_devices is not None:
        if not 1 <= n_devices <= len(devices):
            raise ValueError(
                f"n_devices={n_devices} outside [1, {len(devices)}] — "
                f"have {device_topology(devices)}")
        devices = devices[:n_devices]
    return devices


def make_cell_mesh(n_devices: int | None = None, *,
                   axis_name: str = CELL_AXIS, devices=None) -> Mesh:
    """1-D mesh over the first ``n_devices`` (default: all) global
    devices; ``devices=`` pins an explicit layout.

    The cell axis is embarrassingly parallel, so grid sharding wants a
    flat mesh regardless of how production training meshes are shaped
    (``repro.launch.mesh`` re-exports this for drivers). Under
    ``jax.distributed`` the default spans every process's devices in
    process order.
    """
    return Mesh(np.array(_device_slice(n_devices, devices)), (axis_name,))


def make_client_mesh(n_devices: int | None = None, *, devices=None) -> Mesh:
    """1-D ``("clients",)`` mesh: within-cell client-axis sharding only
    (DESIGN.md §8). The population capacity must divide the mesh size.
    Under ``jax.distributed`` the default layout spans processes — the
    ROADMAP's client-axis-onto-host-axis mapping, where the only
    per-step collective crossing hosts is the ``(P,)`` reduction."""
    return Mesh(np.array(_device_slice(n_devices, devices)), (CLIENT_AXIS,))


def make_grid_mesh(cells: int, clients: int, *, devices=None) -> Mesh:
    """2-D ``(cells, clients)`` mesh over the first ``cells·clients``
    devices: cell sharding across the first axis composed with
    within-cell client sharding across the second. ``devices=`` pins an
    explicit layout (e.g. a process-spanning one —
    :func:`make_multihost_mesh` builds the canonical version)."""
    pool = list(jax.devices()) if devices is None else list(np.ravel(devices))
    if cells * clients > len(pool):
        raise ValueError(
            f"make_grid_mesh(cells={cells}, clients={clients}) needs "
            f"{cells * clients} global devices, have "
            f"{device_topology(pool)}")
    devices = _device_slice(cells * clients, pool)
    return Mesh(np.array(devices).reshape(cells, clients),
                (CELL_AXIS, CLIENT_AXIS))


def _devices_by_process() -> list[list]:
    """Global devices grouped by owning process, both in stable order."""
    by_proc: dict[int, list] = {}
    for d in jax.devices():
        by_proc.setdefault(d.process_index, []).append(d)
    return [by_proc[p] for p in sorted(by_proc)]


def make_multihost_mesh(cells: int | None = None,
                        clients: int | None = None) -> Mesh:
    """2-D ``(cells, clients)`` mesh with the **cell axis crossing
    processes** and every client-axis row inside one process
    (DESIGN.md §13): the within-cell reduction never crosses a host;
    only the cell axis spans the interconnect, and the cell axis has no
    per-step collective at all.

    Defaults: ``cells`` = the process count (one cell shard per host),
    ``clients`` = the local devices each cell row can use. For the dual
    layout — client axis across hosts, the ROADMAP's ``(P,)``-psum
    mapping — use :func:`make_client_mesh`, whose global-device default
    already spans processes; for a process-spanning 1-D cells mesh use
    :func:`make_cell_mesh` (global devices are process-major).

    Single-process sessions degenerate to ``make_grid_mesh`` layouts,
    so the same driver code runs anywhere.
    """
    grid = _devices_by_process()
    n_proc, local = len(grid), min(len(g) for g in grid)
    cells = n_proc if cells is None else int(cells)
    if cells % n_proc != 0:
        raise ValueError(
            f"make_multihost_mesh(cells={cells}): the cell axis must "
            f"divide evenly over processes — have {device_topology()}")
    rows_per_proc = cells // n_proc
    width = local // rows_per_proc if clients is None else int(clients)
    if width < 1 or rows_per_proc * width > local:
        raise ValueError(
            f"make_multihost_mesh(cells={cells}, clients={clients}) needs "
            f"{rows_per_proc}×{width} devices per process, have "
            f"{local} local — {device_topology()}")
    rows = [g[r * width:(r + 1) * width]
            for g in grid for r in range(rows_per_proc)]
    return Mesh(np.array(rows), (CELL_AXIS, CLIENT_AXIS))


def _mesh_axes(mesh: Mesh) -> tuple[str | None, str | None]:
    """(cell_axis, client_axis) names of a grid mesh, either possibly
    None. 1-D meshes keep the legacy rule — any axis name is the cell
    axis — unless the axis is literally named ``"clients"``; 2-D meshes
    must be (cell_axis, "clients")."""
    names = mesh.axis_names
    if len(names) == 1:
        if names[0] == CLIENT_AXIS:
            return None, CLIENT_AXIS
        return names[0], None
    if len(names) == 2 and names[1] == CLIENT_AXIS \
            and names[0] != CLIENT_AXIS:
        return names[0], CLIENT_AXIS
    raise ValueError(
        "grid sharding needs a 1-D mesh (the flattened cell axis, or a "
        f"'{CLIENT_AXIS}' axis for within-cell sharding) or a 2-D "
        f"(cells, '{CLIENT_AXIS}') mesh; got axes {names} — build one "
        "with make_cell_mesh() / make_client_mesh() / make_grid_mesh()")


def _cell_axis(mesh: Mesh) -> str:
    """Legacy validator: the (sole) cell axis of a cells-only mesh."""
    cell_ax, client_ax = _mesh_axes(mesh)
    if client_ax is not None or cell_ax is None:
        raise ValueError(
            f"expected a cells-only 1-D mesh, got axes {mesh.axis_names}")
    return cell_ax


def _check_client_shards(n_cap: int, shards: int) -> int:
    if n_cap % shards != 0:
        raise ValueError(
            f"client-axis sharding needs the population capacity to divide "
            f"the '{CLIENT_AXIS}' mesh axis: N_cap={n_cap} over {shards} "
            f"shards (pad the population to a multiple — DESIGN.md §8)")
    return n_cap // shards


def client_leaf_specs(tree, n_cap: int, *, client_axis: str,
                      cell_axis: str | None = None, lead: int = 0):
    """Per-leaf ``PartitionSpec`` list (``tree_leaves`` order) for a
    component under client sharding: a leaf whose axis ``lead`` (the
    first post-batch axis) has the population capacity ``n_cap`` is
    treated as per-client and sharded over ``client_axis``; every other
    leaf (scalar hyperparameters) is replicated across it. ``lead=1``
    with ``cell_axis`` set prepends cell sharding on axis 0 (the grid
    path). Returned as a flat list — the sharded runners pass component
    *leaves* through ``shard_map`` and unflatten inside the body, so
    registered-dataclass constructors only ever see (local) arrays.

    The rule is shape-based: a non-per-client hyperparameter vector that
    coincidentally has length ``n_cap`` on that axis would be sharded
    too — a component with such a leaf must not be run client-sharded
    (none of the built-ins has one).
    """
    lead_spec = (cell_axis,) * lead

    def one(leaf):
        if leaf.ndim > lead and leaf.shape[lead] == n_cap:
            return PartitionSpec(*lead_spec, client_axis)
        return PartitionSpec(*lead_spec)

    return [one(leaf) for leaf in jax.tree_util.tree_leaves(tree)]


def flatten_cells(scheduler, energy, keys, *, n_scenarios: int,
                  active=None, p=None, faults=None):
    """(S-stacked components, (R, 2) keys) → C = S·R flat cell arrays.

    Cell ``c = s·R + r`` pairs scenario ``s`` with seed ``r``, matching
    ``x.reshape(S, R, ...)`` on the way back out. ``active`` / ``p`` are
    the optional (S, N_cap) ragged-population operands, ``faults`` the
    optional S-stacked fault component, repeated over seeds like the
    components (None passes through).
    """
    r = keys.shape[0]
    rep = lambda x: jnp.repeat(x, r, axis=0)
    sch_c = jax.tree_util.tree_map(rep, scheduler)
    en_c = jax.tree_util.tree_map(rep, energy)
    flt_c = jax.tree_util.tree_map(rep, faults)
    active_c = jax.tree_util.tree_map(rep, active)
    p_c = jax.tree_util.tree_map(rep, p)
    keys_c = jnp.tile(keys, (n_scenarios, 1))
    return sch_c, en_c, flt_c, active_c, p_c, keys_c


def pad_cells(tree, n_cells: int, n_devices: int):
    """Pad the leading cell axis to a multiple of ``n_devices`` by
    repeating cell 0 (valid data — no NaN lanes); returns the padded
    tree and the padded count."""
    pad = (-n_cells) % n_devices
    if pad == 0:
        return tree, n_cells

    def _pad(x):
        return jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])

    return jax.tree_util.tree_map(_pad, tree), n_cells + pad


@partial(jax.jit,
         static_argnames=("sim", "num_steps", "eval_fn", "eval_every", "mesh",
                          "reduction", "replicate_out"))
def _run_group_sharded(scheduler, energy, faults, active, p, params0, keys,
                       *, sim, num_steps: int, eval_fn=None,
                       eval_every: int = 0, mesh: Mesh,
                       reduction: str = "psum", replicate_out: bool = False):
    """shard_map'd twin of ``engine._run_group``.

    ``scheduler`` / ``energy`` / ``keys`` leaves carry a leading
    (device-divisible) flat cell axis, as do the optional
    ``active`` / ``p`` ragged-population operands (both None for
    uniform cells-only grids); ``params0`` is replicated. Each device
    vmaps the simulator scan over its local cells. When the mesh
    carries a ``clients`` axis, each cell's per-client operands are
    additionally sharded over it and the simulator runs under a
    :func:`repro.core.energy.client_sharding` context (DESIGN.md §8) —
    ``p`` is then always materialized by the caller. Compiled once per
    (sim, group structure, mesh) — probe
    ``_run_group_sharded._cache_size()`` to assert trace counts.
    """
    from repro.experiments.engine import CellResult

    cell_ax, client_ax = _mesh_axes(mesh)
    cells = PartitionSpec(cell_ax) if cell_ax is not None else PartitionSpec()
    replicated = PartitionSpec()
    sch_leaves, sch_def = jax.tree_util.tree_flatten(scheduler)
    en_leaves, en_def = jax.tree_util.tree_flatten(energy)
    flt_leaves, flt_def = jax.tree_util.tree_flatten(faults)
    if flt_leaves and client_ax is not None:
        raise ValueError(
            "fault injection is not supported under a clients mesh axis "
            "(DESIGN.md §10) — use a cells-only mesh or drop the fault "
            "component")

    if client_ax is None:
        in_specs = ([cells] * len(sch_leaves), [cells] * len(en_leaves),
                    [cells] * len(flt_leaves),
                    cells, cells, cells, replicated)
        out_specs = cells
    else:
        n_cap = int(sim.p.shape[0])
        _check_client_shards(n_cap, mesh.shape[client_ax])
        percell = lambda t: client_leaf_specs(
            t, n_cap, client_axis=client_ax, cell_axis=cell_ax, lead=1)
        rows = PartitionSpec(cell_ax, client_ax)
        in_specs = (percell(scheduler), percell(energy), [], rows, rows,
                    cells, replicated)
        out_specs = CellResult(
            params=cells,
            history=SimHistory(loss=cells,
                               participation=PartitionSpec(
                                   cell_ax, None, client_ax),
                               weight_sum=cells,
                               finite=cells),
            evals=cells)

    def local(sch_lv, en_lv, flt_lv, act, pw, ks, p0):
        sch = jax.tree_util.tree_unflatten(sch_def, sch_lv)
        en = jax.tree_util.tree_unflatten(en_def, en_lv)
        flt = jax.tree_util.tree_unflatten(flt_def, flt_lv)

        def one(s, e, f, a, w, k):
            out = sim.run(k, p0, num_steps, scheduler=s, energy=e, faults=f,
                          p=w, active_mask=a,
                          eval_fn=eval_fn, eval_every=eval_every)
            return CellResult(*out) if eval_fn is not None \
                else CellResult(*out, None)

        over_cells = jax.vmap(one, in_axes=(0, 0, 0, 0, 0, 0))
        if client_ax is None:
            return over_cells(sch, en, flt, act, pw, ks)
        shards = mesh.shape[client_ax]
        sch = shard_scheduler(sch, int(sim.p.shape[0]) // shards)
        with client_sharding(client_ax, shards, reduction):
            return over_cells(sch, en, flt, act, pw, ks)

    fn = shard_map(local, mesh=mesh, in_specs=in_specs,
                   out_specs=out_specs, check_vma=False)
    out = fn(sch_leaves, en_leaves, flt_leaves, active, p, keys, params0)
    if replicate_out:
        # Multi-process dispatch: assemble fully-replicated outputs
        # *inside* this executable (a compiler-scheduled all-gather) so
        # every process can read results locally. Fetching sharded
        # outputs with per-leaf host-side allgathers instead is racy on
        # the gloo CPU transport — concurrent mixed-size collectives
        # from separate executables collide (DESIGN.md §13).
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, PartitionSpec()))
    return out


@partial(jax.jit,
         static_argnames=("sim", "num_steps", "eval_fn", "eval_every", "mesh",
                          "reduction", "replicate_out"))
def _run_cell_client_sharded(scheduler, energy, active, p, params0, key, *,
                             sim, num_steps: int, eval_fn=None,
                             eval_every: int = 0, mesh: Mesh,
                             reduction: str = "psum",
                             replicate_out: bool = False):
    """Single-cell client-sharded execution: one population spanning the
    whole ``clients`` mesh (no cell axis, no cell vmap)."""
    client_ax = CLIENT_AXIS
    n_cap = int(sim.p.shape[0])
    shards = mesh.shape[client_ax]
    n_local = _check_client_shards(n_cap, shards)
    percell = lambda t: client_leaf_specs(t, n_cap, client_axis=client_ax)
    rows, replicated = PartitionSpec(client_ax), PartitionSpec()
    hist = SimHistory(loss=replicated,
                      participation=PartitionSpec(None, client_ax),
                      weight_sum=replicated,
                      finite=replicated)
    out_specs = (replicated, hist) if eval_fn is None \
        else (replicated, hist, replicated)
    sch_leaves, sch_def = jax.tree_util.tree_flatten(scheduler)
    en_leaves, en_def = jax.tree_util.tree_flatten(energy)

    def local(sch_lv, en_lv, act, pw, k, p0):
        sch = shard_scheduler(
            jax.tree_util.tree_unflatten(sch_def, sch_lv), n_local)
        en = jax.tree_util.tree_unflatten(en_def, en_lv)
        with client_sharding(client_ax, shards, reduction):
            return sim.run(k, p0, num_steps, scheduler=sch, energy=en,
                           p=pw, active_mask=act,
                           eval_fn=eval_fn, eval_every=eval_every)

    fn = shard_map(local, mesh=mesh,
                   in_specs=(percell(scheduler), percell(energy), rows, rows,
                             replicated, replicated),
                   out_specs=out_specs, check_vma=False)
    out = fn(sch_leaves, en_leaves, active, p, key, params0)
    if replicate_out:
        # See _run_group_sharded: in-executable assembly for the
        # multi-process return boundary.
        out = jax.lax.with_sharding_constraint(
            out, jax.sharding.NamedSharding(mesh, PartitionSpec()))
    return out


def clear_cache() -> None:
    """Drop compiled sharded-grid executables (see engine.clear_cache)."""
    _run_group_sharded.clear_cache()
    _run_cell_client_sharded.clear_cache()


# ------------------------------------------- multi-process host boundary

def replicate_to_mesh(tree, mesh: Mesh):
    """Lift host-local arrays to *replicated* global ``jax.Array``s.

    Every process in a multi-controller session computes the identical
    host-side grid (same scenarios, same padding, same PRNG keys), so
    each already holds the full value of every operand — the lift is
    pure bookkeeping: each process populates its addressable shards
    from its local copy, no data moves. The jitted ``shard_map``s then
    reshard replicated → cells/clients-sharded internally, which is
    local slicing under SPMD. None leaves pass through.
    """
    sharding = jax.sharding.NamedSharding(mesh, PartitionSpec())

    def one(x):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx, x=x: x[idx])

    return jax.tree_util.tree_map(one, tree)


def fetch_to_host(tree):
    """Materialize global result arrays as numpy on **every** process —
    the return boundary of a multi-process dispatch.

    The runners request fully-replicated outputs (``replicate_out=True``
    lowers the assembly all-gather into the compiled executable), so the
    common path is a plain local read. A leaf that still arrives sharded
    (outputs of user ``eval_fn``s routed around the runners) falls back
    to a host-driven allgather — correct, but serialized per leaf, since
    concurrent mixed-size collectives from separate executables collide
    on the gloo CPU transport (DESIGN.md §13). Downstream host-side
    assembly (crop, divergence attach, GridResult) then runs unchanged
    on all hosts."""
    from jax.experimental import multihost_utils

    def one(x):
        if getattr(x, "is_fully_addressable", True) or \
                getattr(x, "is_fully_replicated", False):
            return np.asarray(x)
        return np.asarray(multihost_utils.process_allgather(x, tiled=True))

    return jax.tree_util.tree_map(one, tree)


def run_client_sharded(sim, key, params0, num_steps: int, *, scheduler=None,
                       energy=None, mesh: Mesh, p=None, active_mask=None,
                       eval_fn=None, eval_every: int = 0,
                       reduction: str = "psum"):
    """Run ONE cell with its client axis sharded across ``mesh``.

    The within-cell entry point (DESIGN.md §8) for populations a single
    device cannot hold: arrivals/battery state, scheduler rows,
    ``active_mask``/``p`` and the ``(N, P)`` gradient buffer live
    sharded over the mesh's ``clients`` axis; params and optimizer state
    stay replicated. Same signature contract as
    :meth:`ClientSimulator.run` (returns ``(params, history[, evals])``
    with the participation history assembled back to the full client
    axis). The default ``reduction="psum"`` is bandwidth-optimal (the
    collective moves P floats, not N·P — float32 reassociation
    tolerance vs the unsharded run); ``reduction="gather"`` is the
    bit-for-bit differential oracle, and ``"fused[_bf16]"`` /
    ``"psum_bf16"`` select the fused reduce-and-update kernel and/or a
    bf16 wire (DESIGN.md §9 decision table). The capacity ``len(sim.p)``
    must divide the mesh's client-axis size.
    """
    cell_ax, client_ax = _mesh_axes(mesh)
    if client_ax is None:
        raise ValueError(
            f"run_client_sharded needs a mesh with a '{CLIENT_AXIS}' axis; "
            f"got axes {mesh.axis_names}")
    if cell_ax is not None and mesh.shape[cell_ax] != 1:
        raise ValueError(
            "run_client_sharded executes a single cell — the mesh's cell "
            f"axis must have size 1, got {mesh.shape[cell_ax]}")
    scheduler = sim.scheduler if scheduler is None else scheduler
    energy = sim.energy if energy is None else energy
    if scheduler is None or energy is None:
        raise ValueError("scheduler/energy must be given (or set on sim)")
    if p is None:
        p = sim.p
    args = (scheduler, energy, active_mask, p, params0, key)
    multiprocess = mesh_process_count(mesh) > 1
    if multiprocess:
        args = replicate_to_mesh(args, mesh)
    out = _run_cell_client_sharded(
        *args, sim=sim, num_steps=num_steps, eval_fn=eval_fn,
        eval_every=eval_every, mesh=mesh, reduction=reduction,
        replicate_out=multiprocess)
    return fetch_to_host(out) if multiprocess else out


def run_group_sharded(scheduler, energy, active, p, params0, keys, *, sim,
                      num_steps: int, n_scenarios: int, mesh: Mesh,
                      faults=None, eval_fn=None, eval_every: int = 0,
                      reduction: str = "psum"):
    """Execute one structure-group's (S × R) cell block across ``mesh``.

    Flatten → pad → shard_map → slice off padding → reshape to (S, R).
    ``active`` / ``p`` are the optional (S, N_cap) ragged-population
    operands (engine-level client padding; DESIGN.md §7), sharded along
    the cell axis exactly like the components. Per-cell numerics match
    the vmap path to float32 reassociation tolerance (each cell is the
    same ``ClientSimulator.run`` under the same per-seed PRNG key).

    A mesh carrying a ``clients`` axis additionally shards every
    per-client operand of every cell across it (DESIGN.md §8);
    ``reduction`` selects the cross-shard aggregation — ``"psum"``
    (default, bandwidth-optimal), ``"gather"`` (the bitwise oracle), or
    ``"fused[_bf16]"`` / ``"psum_bf16"`` (DESIGN.md §9).
    """
    cell_ax, client_ax = _mesh_axes(mesh)  # validate before any device work
    if client_ax is not None and (
            faults is not None or sim.faults is not None):
        raise ValueError(
            "fault injection is not supported under a clients mesh axis "
            "(DESIGN.md §10) — use a cells-only mesh or drop the fault "
            "component")
    r = keys.shape[0]
    n_cells = n_scenarios * r
    if client_ax is not None and p is None:
        # The simulator's constructor default cannot be used sharded —
        # the closed-over full (N,) vector would be replicated against
        # (n_local,) decisions — so materialize it as a sharded operand.
        p = jnp.broadcast_to(sim.p, (n_scenarios,) + sim.p.shape)
    sch_c, en_c, flt_c, active_c, p_c, keys_c = flatten_cells(
        scheduler, energy, keys, n_scenarios=n_scenarios, active=active, p=p,
        faults=faults)
    cell_shards = mesh.shape[cell_ax] if cell_ax is not None else 1
    (sch_c, en_c, flt_c, active_c, p_c, keys_c), _ = pad_cells(
        (sch_c, en_c, flt_c, active_c, p_c, keys_c), n_cells, cell_shards)
    args = (sch_c, en_c, flt_c, active_c, p_c, params0, keys_c)
    multiprocess = mesh_process_count(mesh) > 1
    if multiprocess:
        args = replicate_to_mesh(args, mesh)
    out = _run_group_sharded(*args, sim=sim, num_steps=num_steps,
                             eval_fn=eval_fn, eval_every=eval_every,
                             mesh=mesh, reduction=reduction,
                             replicate_out=multiprocess)
    if multiprocess:
        out = fetch_to_host(out)
    return jax.tree_util.tree_map(
        lambda x: x[:n_cells].reshape((n_scenarios, r) + x.shape[1:]), out)
