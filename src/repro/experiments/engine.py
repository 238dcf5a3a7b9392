"""Grid-batched scenario execution: one compiled computation per group.

The paper's headline evidence is a *grid* of runs — schedulers × arrival
processes × seeds. Because schedulers and energy processes are
registered pytrees (see :mod:`repro.core.energy` /
:mod:`repro.core.scheduling`), a whole grid collapses into a handful of
compiled computations:

1. Scenarios are grouped by the **pytree structure** of their built
   (scheduler, energy) pair — same dataclass types, same static
   metadata, same leaf shapes/dtypes.
2. Each group's component leaves are stacked along a new scenario axis.
3. One jitted function (:data:`_run_group`) runs
   ``vmap(scenarios) ∘ vmap(seeds)`` over :meth:`ClientSimulator.run`'s
   ``lax.scan`` — so XLA traces and compiles **once per group**, not
   once per (scenario, seed) cell.

**Ragged client populations** (DESIGN.md §7): when scenarios differ in
``n_clients``, the client count becomes a *data* axis instead of a
*shape* axis — every cell's per-client component leaves are padded to
the simulator's population capacity ``N_cap = len(sim.p)``, an
``active_mask`` marks the rows that exist, and each cell carries its
own zero-padded data weights (``subpopulation_p``). All population
sizes of one scheduler × arrival family then share a **single**
compiled computation, and masked rows contribute exactly zero gradient
and zero scheduler probability mass — per-cell numerics are bit-for-bit
those of the natural-N run (``tests/test_ragged.py``).

:func:`run_grid_sequential` executes the identical cells one traced scan
at a time — the pre-refactor execution model — and exists for numerical
cross-checks and wall-clock comparison (``benchmarks/fig1.py`` times
both).
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
from functools import partial
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.core.trainer import ClientSimulator, SimHistory
from repro.experiments.scenario import Scenario

_LOG = logging.getLogger("repro.experiments.engine")


class CellResult(NamedTuple):
    """Per-scenario result; every leaf carries a leading seed axis R.

    params   : final model parameters, leaves (R, ...)
    history  : SimHistory with leaves (R, T, ...)
    evals    : eval_fn outputs with leaves (R, num_evals, ...), or None
    diverged : (R,) int32 — first step index at which the seed's params
               went non-finite (−1: the run stayed finite throughout).
               The per-cell quarantine record (DESIGN.md §10), computed
               from the ``history.finite`` per-step isfinite flags.
    """

    params: Any
    history: SimHistory
    evals: Any = None
    diverged: Any = None


def _group_key(scheduler, energy, faults=None):
    """Hashable trace signature: pytree structure + leaf shapes/dtypes."""
    leaves, treedef = jax.tree_util.tree_flatten((scheduler, energy, faults))
    return treedef, tuple((l.shape, str(l.dtype)) for l in leaves)


def _stack(components):
    """Leaf-wise stack of same-structure pytrees along a new scenario axis."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *components)


def population_mask(n_clients: int, n_total: int) -> jax.Array:
    """(n_total,) float32 mask: 1 for the first ``n_clients`` rows."""
    return (jnp.arange(n_total) < n_clients).astype(jnp.float32)


def subpopulation_p(p, n_clients: int, n_total: int | None = None) -> jax.Array:
    """Data weights of the ``n_clients``-prefix subpopulation of ``p``,
    renormalized over the active rows only and zero-padded to
    ``n_total`` (default ``len(p)``).

    This is *the* unbiasedness-under-masking rule (DESIGN.md §7): the
    paper's p_i = D_i/D must sum to 1 over the clients that exist, so a
    ragged cell's weights are the master prefix renormalized — computed
    here, in f32, by both the padded engine path and (via this shared
    helper) the per-N baselines the equivalence tests compare against.
    """
    p = jnp.asarray(p, jnp.float32)
    n_total = int(p.shape[0]) if n_total is None else int(n_total)
    if not 1 <= n_clients <= n_total:
        raise ValueError(
            f"n_clients={n_clients} outside [1, {n_total}]")
    pref = p[:n_clients] / jnp.sum(p[:n_clients])
    if n_clients == n_total:
        return pref
    return jnp.concatenate(
        [pref, jnp.zeros((n_total - n_clients,), jnp.float32)])


def _pad_built(built, n_cap: int):
    """(scheduler, energy, faults) built at natural n → padded to n_cap
    rows (``faults`` may be None)."""
    from repro.core.energy import pad_arrivals
    from repro.core.faults import pad_faults
    from repro.core.scheduling import pad_scheduler

    scheduler, energy, faults = built
    return (pad_scheduler(scheduler, n_cap), pad_arrivals(energy, n_cap),
            pad_faults(faults, n_cap))


def _cell_mask_p(sc: "Scenario", sim: ClientSimulator, n_cap: int):
    """(active_mask, p) for one cell of a ragged group. A full-capacity
    cell gets an all-ones mask and the caller's ``sim.p``
    *unrenormalized*: multiplying by 1.0 and reusing p verbatim keeps it
    bit-identical to the unmasked run, whereas renormalizing would
    perturb it whenever p does not sum to exactly 1.0 in f32."""
    if sc.n_clients == n_cap:
        return jnp.ones((n_cap,), jnp.float32), sim.p
    return (population_mask(sc.n_clients, n_cap),
            subpopulation_p(sim.p, sc.n_clients, n_cap))


class StructureGroup(NamedTuple):
    """One structure group of a resolved grid — the leaf-stacked
    component batch the engine dispatches as ONE compiled computation.

    ``key`` is the :func:`_group_key` trace signature; ``members`` index
    into the caller's scenario list; ``scheduler`` / ``energy`` /
    ``faults`` carry a leading scenario axis S (``faults`` is None for
    fault-free groups); ``active`` / ``p`` are the (S, N_cap) ragged
    operands, both None when the group is uniformly at capacity.
    """

    key: Any
    members: list[int]
    scheduler: Any
    energy: Any
    faults: Any
    active: Any
    p: Any
    ragged: bool


def resolve_structure_groups(
    scenarios: Sequence[Scenario], *, sim: ClientSimulator,
) -> tuple[list[str], int, list[StructureGroup]]:
    """Group scenario cells by padded component structure.

    The shared front half of every batched execution path
    (:func:`execute_cells` and :func:`execute_cells_resumable` resolve
    through here, so both agree on names, padding, raggedness and group
    membership — which is what makes the chunked path bitwise the
    unchunked one). Below-capacity components are padded to
    ``N_cap = len(sim.p)`` (an identity at capacity) and grouping is on
    the padded structure; raggedness is decided per group, so uniform
    groups keep their mask-free compiled programs.

    Returns ``(names, n_cap, groups)`` in input order.
    """
    scenarios = list(scenarios)
    names = check_unique_names(scenarios)
    n_cap = int(sim.p.shape[0])
    over = [f"{sc.name} (N={sc.n_clients})" for sc in scenarios
            if sc.n_clients > n_cap]
    if over:
        raise ValueError(
            f"scenario population exceeds the simulator capacity "
            f"N_cap={n_cap} (len(sim.p)): {over}")
    built = [sc.build() + (sc.build_faults(),) for sc in scenarios]
    padded = [b if sc.n_clients == n_cap else _pad_built(b, n_cap)
              for sc, b in zip(scenarios, built)]
    grouped: dict[Any, list[int]] = {}
    for idx, (sch, en, flt) in enumerate(padded):
        grouped.setdefault(_group_key(sch, en, flt), []).append(idx)

    groups = []
    for gkey, members in grouped.items():
        ragged = any(scenarios[i].n_clients != n_cap for i in members)
        sch_batch = _stack([padded[i][0] for i in members])
        en_batch = _stack([padded[i][1] for i in members])
        # A fault-free group's components are all None — tree_map over
        # all-None pytrees has no leaves and returns None, so the group
        # dispatches the pre-fault-layer program verbatim.
        flt_batch = _stack([padded[i][2] for i in members])
        active_batch, p_batch = None, None
        if ragged:
            masks, ps = zip(*(_cell_mask_p(scenarios[i], sim, n_cap)
                              for i in members))
            active_batch, p_batch = jnp.stack(masks), jnp.stack(ps)
        groups.append(StructureGroup(gkey, members, sch_batch, en_batch,
                                     flt_batch, active_batch, p_batch,
                                     ragged))
    return names, n_cap, groups


def _crop_cell(cell: "CellResult", n: int, n_cap: int) -> "CellResult":
    """Slice the padded client axis of per-client outputs back to n."""
    if n == n_cap:
        return cell
    hist = cell.history._replace(
        participation=cell.history.participation[..., :n])
    return cell._replace(history=hist)


def _attach_divergence(cell: "CellResult") -> "CellResult":
    """Fill ``CellResult.diverged`` from the per-step isfinite flags.

    Host-side post-processing (the flags were the cheap in-scan
    reduction); ``diverged[r]`` is the first step index whose post-step
    params were non-finite for seed r, or −1 when the whole run stayed
    finite. Divergence is absorbing under every built-in optimizer
    (NaN params → NaN grads → NaN params), so first-bad-step plus the
    flag tail fully characterize the quarantined trajectory.
    """
    fin = cell.history.finite
    if fin is None:  # hand-built history without flags — nothing to report
        return cell
    bad = ~np.asarray(fin)
    first = np.where(bad.any(axis=-1), bad.argmax(axis=-1), -1)
    return cell._replace(diverged=jnp.asarray(first, jnp.int32))


def divergence_summary(results: dict[str, "CellResult"]) -> dict[str, dict]:
    """Per-cell quarantine stats: ``{name: {n_diverged, first_bad_step}}``.

    ``first_bad_step`` is the earliest diverged seed's first non-finite
    step (−1 when every seed stayed finite). The same numbers surface
    per-study through :meth:`repro.experiments.GridResult.divergence`.
    """
    out = {}
    for name, cell in results.items():
        d = np.asarray(cell.diverged) if cell.diverged is not None \
            else np.array([-1])
        bad = d[d >= 0]
        out[name] = {"n_diverged": int(bad.size),
                     "first_bad_step": int(bad.min()) if bad.size else -1}
    return out


def _group_body(scheduler, energy, faults, active, p, params0, keys, *,
                sim: ClientSimulator, num_steps: int, eval_fn=None,
                eval_every: int = 0):
    """vmap(scenario axis) ∘ vmap(seed axis) over one simulator scan —
    the shared computation behind :data:`_run_group` (process-global jit
    cache) and :func:`make_group_runner` (per-instance evictable cache,
    the serve layer's executable store). Both wrappers trace the same
    body, so their compiled programs are identical and results are
    bitwise interchangeable."""

    def one(sch, en, flt, act, pw, key):
        out = sim.run(key, params0, num_steps, scheduler=sch, energy=en,
                      faults=flt, p=pw, active_mask=act,
                      eval_fn=eval_fn, eval_every=eval_every)
        return CellResult(*out) if eval_fn is not None else CellResult(*out, None)

    over_seeds = jax.vmap(one, in_axes=(None, None, None, None, None, 0))
    over_scenarios = jax.vmap(over_seeds, in_axes=(0, 0, 0, 0, 0, None))
    return over_scenarios(scheduler, energy, faults, active, p, keys)


@partial(jax.jit, static_argnames=("sim", "num_steps", "eval_fn", "eval_every"))
def _run_group(scheduler, energy, faults, active, p, params0, keys, *,
               sim: ClientSimulator, num_steps: int, eval_fn=None,
               eval_every: int = 0):
    """Process-global jit wrapper of :func:`_group_body`.

    ``scheduler`` / ``energy`` / ``faults`` leaves carry a leading
    scenario axis S (``faults`` is None for fault-free groups);
    ``active`` / ``p`` are (S, N_cap) ragged-population operands (both
    None for uniform grids); ``keys`` is (R, 2). Compiled once per
    (sim, group structure) — probe ``_run_group._cache_size()`` to
    assert trace counts.

    The static ``sim`` / ``eval_fn`` are hashed by identity, so each
    distinct closure (and the datasets it captures) stays referenced by
    the jit cache for process lifetime. Benchmarks and tests are short
    lived; a long-running service issuing many distinct grids should
    route execution through an ``executable_cache``
    (:class:`repro.serve.ExecutableCache` — bounded, per-entry eviction)
    or call :func:`clear_cache` between sweeps.
    """
    return _group_body(scheduler, energy, faults, active, p, params0, keys,
                       sim=sim, num_steps=num_steps, eval_fn=eval_fn,
                       eval_every=eval_every)


def make_group_runner(*, sim: ClientSimulator, num_steps: int, eval_fn=None,
                      eval_every: int = 0, on_trace=None):
    """A *fresh* jit wrapper around :func:`_group_body`.

    Unlike :data:`_run_group` — whose cache is process-global and only
    clearable wholesale — each runner owns its jit cache, so dropping
    the runner (e.g. on LRU eviction from
    :class:`repro.serve.ExecutableCache`) releases its compiled
    executables and the closures they pin. ``on_trace`` is called each
    time the body is (re)traced — i.e. on every new compilation — which
    is how the serve layer counts compiles without jax internals.
    """

    def _runner(scheduler, energy, faults, active, p, params0, keys):
        if on_trace is not None:
            on_trace()
        return _group_body(scheduler, energy, faults, active, p, params0,
                           keys, sim=sim, num_steps=num_steps,
                           eval_fn=eval_fn, eval_every=eval_every)

    return jax.jit(_runner)


def structure_fingerprint(group_key) -> str:
    """Short stable digest of a :func:`_group_key` trace signature —
    the cache-key / response-visible name of one component structure."""
    return hashlib.sha256(str(group_key).encode()).hexdigest()[:12]


def clear_cache() -> None:
    """Drop compiled grid executables (and the sim/eval_fn closures —
    with their captured datasets — that the jit cache keeps alive),
    for both the vmap and shard_map execution paths."""
    _run_group.clear_cache()
    from repro.experiments import placement

    placement.clear_cache()


def _seed_keys(seeds):
    if isinstance(seeds, int):
        seeds = range(seeds)
    seeds = list(seeds)
    if not seeds:
        raise ValueError("need at least one seed")
    return seeds, jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])


def check_unique_names(scenarios: Sequence[Scenario]) -> list[str]:
    """Scenario names key the result mapping — duplicates would silently
    overwrite cells. Shared by every execution path (batched, sequential,
    Study.resolve)."""
    names = [sc.name for sc in scenarios]
    if len(set(names)) != len(names):
        dups = sorted({n for n in names if names.count(n) > 1})
        raise ValueError(
            f"scenario names must be unique, got duplicates {dups} in {names}")
    return names


def _resolve_sim(sim, grads_fn, p, optimizer, loss_fn, use_kernel):
    if sim is not None:
        return sim
    if grads_fn is None or p is None or optimizer is None:
        raise ValueError(
            "either pass a prebuilt sim= or all of grads_fn/p/optimizer")
    return ClientSimulator(grads_fn=grads_fn, p=p, optimizer=optimizer,
                           loss_fn=loss_fn, use_kernel=use_kernel)


# ------------------------------------------------- graceful degradation

#: Reduction fallback order (DESIGN.md §10): each step strips one
#: requirement — fused kernel first, then the bf16 wire, then the psum
#: collective — ending at ``gather``, the bitwise-oracle path with no
#: mesh-shape preconditions beyond a divisible cell axis.
_REDUCTION_LADDER: dict[str, tuple[str, ...]] = {
    "fused_bf16": ("psum_bf16", "psum", "gather"),
    "fused": ("psum", "gather"),
    "psum_bf16": ("psum", "gather"),
    "psum": ("gather",),
    "gather": (),
}


@dataclasses.dataclass(frozen=True)
class DowngradeRecord:
    """One structured graceful-degradation event (DESIGN.md §10).

    ``stage`` is the ladder rung that moved: ``"reduction"`` (client
    cross-shard aggregation fell one step down :data:`_REDUCTION_LADDER`)
    or ``"placement"`` (the sharded executor was abandoned for the
    single-device vmap path). ``group`` names the scenario cells that
    were re-dispatched; ``error`` is the stringified ValueError that
    triggered the move.
    """

    group: tuple[str, ...]
    stage: str
    from_value: str
    to_value: str
    error: str

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)


_LAST_DOWNGRADES: list[DowngradeRecord] = []


def last_downgrades() -> tuple[DowngradeRecord, ...]:
    """Downgrade records from the most recent degradable execution.

    Reset at the start of every :func:`execute_cells` call; empty means
    every group ran at its requested placement/reduction."""
    return tuple(_LAST_DOWNGRADES)


def _record_downgrade(group, stage, frm, to, err) -> DowngradeRecord:
    rec = DowngradeRecord(group=tuple(group), stage=stage,
                          from_value=str(frm), to_value=str(to),
                          error=str(err))
    _LAST_DOWNGRADES.append(rec)
    _LOG.warning("degraded %s %s -> %s: %s", stage, frm, to, rec.to_json())
    return rec


@tracing.span("engine.execute")
def execute_cells(
    scenarios: Sequence[Scenario],
    *,
    sim: ClientSimulator,
    params0,
    num_steps: int,
    seeds: int | Sequence[int] = 8,
    eval_fn=None,
    eval_every: int = 0,
    mesh=None,
    sequential: bool = False,
    client_reduction: str = "psum",
    degrade: bool = False,
    executable_cache=None,
) -> dict[str, CellResult]:
    """Execute scenario × seed cells with a prebuilt simulator.

    The single execution core behind :meth:`Study.run` and the legacy
    :func:`run_grid` / :func:`run_grid_sequential` shims. Batched mode
    groups cells by component structure and runs one compiled
    vmap(scenarios)∘vmap(seeds) computation per group (sharded across
    ``mesh`` when given); ``sequential=True`` runs one traced scan per
    cell — the pre-refactor model kept for cross-checks and timing.

    Populations may be **ragged**: scenarios whose ``n_clients`` differ
    from the simulator's capacity ``N_cap = len(sim.p)`` are padded to
    N_cap with an active-row mask and per-cell renormalized weights
    (:func:`subpopulation_p`), so every population size of one
    scheduler × arrival structure shares a single compiled computation.
    Raggedness is decided **per structure group**: a group whose members
    are all at full capacity runs the unmasked legacy program
    bit-for-bit (and keeps its jit cache entry) even when other groups
    of the same grid mix populations; a full-capacity cell inside a
    mixed group runs under an all-ones mask with the caller's ``p``
    verbatim — also bit-identical. Per-client outputs
    (``history.participation``) are cropped back to the natural n.
    ``grads_fn`` must always emit N_cap rows — ragged cells simply
    ignore the rows of clients that don't exist.

    ``mesh`` may carry a ``clients`` axis (1-D ``make_client_mesh`` or
    2-D ``make_grid_mesh``, DESIGN.md §8): each cell's client axis is
    then sharded within the cell, ``client_reduction`` selecting the
    cross-shard aggregation — ``"psum"`` (default, bandwidth-optimal,
    f32 tolerance vs the vmap path), ``"gather"`` (bitwise oracle), or
    ``"fused[_bf16]"`` / ``"psum_bf16"`` (fused reduce-and-update kernel
    and/or bf16 wire; DESIGN.md §9).

    ``degrade=True`` arms the graceful-degradation ladder (DESIGN.md
    §10): a group whose sharded dispatch raises ``ValueError`` (mesh
    shape, reduction preconditions, fault/shard conflicts) is retried
    one rung down :data:`_REDUCTION_LADDER`, and when the ladder is
    exhausted, on the single-device vmap path. Every move is logged and
    recorded (:func:`last_downgrades`). Off by default — precondition
    errors raise, as before.

    ``executable_cache`` (vmap path only; DESIGN.md §11) replaces the
    process-global :data:`_run_group` jit cache with a caller-owned
    keyed store: each structure group dispatches through
    ``executable_cache.group_runner((group_key, ragged), sim=...,
    num_steps=..., eval_fn=..., eval_every=...)`` — a
    :func:`make_group_runner`-style jit callable the cache may memoize,
    bound, and evict. This is how :class:`repro.serve.StudyService`
    turns repeat traffic into pure dispatch while keeping executable
    memory bounded.

    Host spans (:mod:`repro.tracing`): ``engine.execute`` around the
    call, ``engine.resolve`` around grouping, and for each group
    ``engine.dispatch`` and ``engine.collect`` (slicing out its cells),
    which holds ``engine.wait`` (the group's first read, until its
    program has run).
    """
    scenarios = list(scenarios)
    del _LAST_DOWNGRADES[:]
    names = check_unique_names(scenarios)
    seed_list, keys = _seed_keys(seeds)

    n_cap = int(sim.p.shape[0])
    over = [f"{sc.name} (N={sc.n_clients})" for sc in scenarios
            if sc.n_clients > n_cap]
    if over:
        raise ValueError(
            f"scenario population exceeds the simulator capacity "
            f"N_cap={n_cap} (len(sim.p)): {over}")

    if sequential:
        if mesh is not None:
            raise ValueError("sequential execution does not take a mesh")
        results = {}
        for sc in scenarios:
            scheduler, energy = sc.build()
            faults = sc.build_faults()
            active, p_cell = (None, None)
            if sc.n_clients != n_cap:
                scheduler, energy, faults = _pad_built(
                    (scheduler, energy, faults), n_cap)
                active, p_cell = _cell_mask_p(sc, sim, n_cap)
            per_seed = []
            for s in seed_list:
                out = sim.run(jax.random.PRNGKey(int(s)), params0, num_steps,
                              scheduler=scheduler, energy=energy,
                              faults=faults, p=p_cell, active_mask=active,
                              eval_fn=eval_fn, eval_every=eval_every)
                cell = CellResult(*out) if eval_fn is not None \
                    else CellResult(*out, None)
                per_seed.append(cell)
            stacked = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *per_seed)
            cell = _crop_cell(stacked, sc.n_clients, n_cap)
            results[sc.name] = _attach_divergence(cell)
        return results

    sharded = mesh is not None and mesh.size > 1
    if sharded:
        from repro.experiments import placement

    with tracing.span("engine.resolve"):
        _, _, groups = resolve_structure_groups(scenarios, sim=sim)

    results: list[CellResult | None] = [None] * len(scenarios)
    for grp in groups:

        def run_vmap(grp=grp):
            if executable_cache is not None:
                runner = executable_cache.group_runner(
                    (grp.key, grp.ragged), sim=sim, num_steps=num_steps,
                    eval_fn=eval_fn, eval_every=eval_every)
                return runner(grp.scheduler, grp.energy, grp.faults,
                              grp.active, grp.p, params0, keys)
            return _run_group(grp.scheduler, grp.energy, grp.faults,
                              grp.active, grp.p, params0, keys, sim=sim,
                              num_steps=num_steps, eval_fn=eval_fn,
                              eval_every=eval_every)

        with tracing.span("engine.dispatch"):
            if sharded:
                member_names = [names[i] for i in grp.members]
                reduction = client_reduction
                while True:
                    try:
                        out = placement.run_group_sharded(
                            grp.scheduler, grp.energy, grp.active, grp.p,
                            params0, keys, sim=sim, num_steps=num_steps,
                            n_scenarios=len(grp.members), mesh=mesh,
                            eval_fn=eval_fn, eval_every=eval_every,
                            reduction=reduction, faults=grp.faults)
                        break
                    except ValueError as e:
                        if not degrade:
                            raise
                        lower = _REDUCTION_LADDER.get(reduction, ())
                        if lower:
                            _record_downgrade(member_names, "reduction",
                                              reduction, lower[0], e)
                            reduction = lower[0]
                            continue
                        _record_downgrade(member_names, "placement",
                                          "sharded", "vmap", e)
                        out = run_vmap()
                        break
            else:
                out = run_vmap()
        with tracing.span("engine.collect"):
            for j, idx in enumerate(grp.members):
                cell = jax.tree_util.tree_map(lambda x: x[j], out)
                cell = _crop_cell(cell, scenarios[idx].n_clients, n_cap)
                if j == 0 and cell.history.finite is not None:
                    # The group's first read (_attach_divergence's
                    # np.asarray) waits for its program: named here.
                    with tracing.span("engine.wait"):
                        jax.block_until_ready(cell.history.finite)
                results[idx] = _attach_divergence(cell)
    return dict(zip(names, results))


def run_grid(
    scenarios: Sequence[Scenario],
    *,
    grads_fn=None,
    p=None,
    optimizer=None,
    params0,
    num_steps: int,
    seeds: int | Sequence[int] = 8,
    loss_fn=None,
    use_kernel: bool = False,
    eval_fn=None,
    eval_every: int = 0,
    sim: ClientSimulator | None = None,
    mesh=None,
) -> dict[str, CellResult]:
    """Execute every scenario × seed cell, batched per component structure.

    .. deprecated:: prefer :meth:`repro.experiments.Study.run`, which
       owns simulator construction and returns a labeled
       :class:`~repro.experiments.GridResult`. This shim remains for
       hand-built irregular scenario lists.

    ``seeds`` is either a count (seeds 0..R−1) or an explicit list; seed
    ``s`` runs under ``jax.random.PRNGKey(s)``, bit-identical to a
    standalone ``ClientSimulator.run(PRNGKey(s), ...)`` of the same cell
    (up to float reassociation introduced by batching).

    ``mesh`` (a ``jax.sharding.Mesh``, e.g.
    :func:`repro.experiments.placement.make_cell_mesh`) shards each
    group's flattened (scenario × seed) cell axis across devices
    (DESIGN.md §5); a mesh with a ``clients`` axis
    (:func:`~repro.experiments.placement.make_client_mesh` /
    :func:`~repro.experiments.placement.make_grid_mesh`) additionally
    shards each cell's client axis within the cell (DESIGN.md §8).
    Without a mesh — or with a 1-device mesh — execution takes the
    single-device vmap path, bit-for-bit as before.

    The jit cache is keyed on ``sim`` by identity, so repeated calls
    with a fresh simulator (or fresh grads_fn/eval_fn lambdas) re-trace
    every group. A driver issuing the same grid many times should build
    the simulator once and pass it via ``sim`` (then grads_fn/p/
    optimizer/loss_fn/use_kernel are taken from it and the keyword
    values are ignored).

    Returns ``{scenario.name: CellResult}`` in input order.
    """
    sim = _resolve_sim(sim, grads_fn, p, optimizer, loss_fn, use_kernel)
    return execute_cells(scenarios, sim=sim, params0=params0,
                         num_steps=num_steps, seeds=seeds, eval_fn=eval_fn,
                         eval_every=eval_every, mesh=mesh)


def run_grid_sequential(
    scenarios: Sequence[Scenario],
    *,
    grads_fn=None,
    p=None,
    optimizer=None,
    params0,
    num_steps: int,
    seeds: int | Sequence[int] = 8,
    loss_fn=None,
    use_kernel: bool = False,
    eval_fn=None,
    eval_every: int = 0,
    sim: ClientSimulator | None = None,
) -> dict[str, CellResult]:
    """The pre-refactor execution model: one traced scan per cell.

    .. deprecated:: prefer ``Study.run(config=ExecutionConfig(
       sequential=True))``. Numerically equivalent to :func:`run_grid`
       (same per-seed keys); kept as the baseline for correctness
       cross-checks and for the batched-vs-sequential wall-clock
       comparison in ``benchmarks/fig1.py``.
    """
    sim = _resolve_sim(sim, grads_fn, p, optimizer, loss_fn, use_kernel)
    return execute_cells(scenarios, sim=sim, params0=params0,
                         num_steps=num_steps, seeds=seeds, eval_fn=eval_fn,
                         eval_every=eval_every, sequential=True)


# --------------------------------------------- preemption-safe execution

#: Manifest schema tag — bump on incompatible layout changes.
MANIFEST_FORMAT = "study-manifest/v1"


@partial(jax.jit, static_argnames=("sim", "spec"))
def _init_group(scheduler, energy, faults, keys, params0, *,
                sim: ClientSimulator, spec):
    """(S, R) batch of fresh scan carries — vmap(scenarios)∘vmap(seeds)
    of :meth:`ClientSimulator.init`. The carry template for checkpoint
    restore is ``jax.eval_shape`` of this function."""

    def one(sch, en, flt, key):
        return sim.init(key, params0, scheduler=sch, energy=en, faults=flt,
                        spec=spec)

    over_seeds = jax.vmap(one, in_axes=(None, None, None, 0))
    return jax.vmap(over_seeds, in_axes=(0, 0, 0, None))(
        scheduler, energy, faults, keys)


def _advance_body(carry, scheduler, energy, faults, active, p, *,
                  sim: ClientSimulator, num_steps: int, spec):
    """Advance an (S, R) carry batch ``num_steps`` rounds — one scan per
    lane under vmap∘vmap, the chunked twin of :func:`_group_body`.
    Because the step stream is a pure function of the carry, chunked
    advancement is bitwise identical to a single uninterrupted scan.
    Shared by :data:`_advance_group` (process-global jit cache) and
    :func:`make_chunk_runner` (per-instance evictable jit, the serve
    layer's resumable executable store)."""

    def one(c, sch, en, flt, act, pw):
        return sim.run_carry(c, num_steps, scheduler=sch, energy=en,
                             faults=flt, p=pw, active_mask=act, spec=spec,
                             donate=False)

    over_seeds = jax.vmap(one, in_axes=(0, None, None, None, None, None))
    return jax.vmap(over_seeds, in_axes=(0, 0, 0, 0, 0, 0))(
        carry, scheduler, energy, faults, active, p)


@partial(jax.jit, static_argnames=("sim", "num_steps", "spec"))
def _advance_group(carry, scheduler, energy, faults, active, p, *,
                   sim: ClientSimulator, num_steps: int, spec):
    """Process-global jit wrapper of :func:`_advance_body`."""
    return _advance_body(carry, scheduler, energy, faults, active, p,
                         sim=sim, num_steps=num_steps, spec=spec)


def make_chunk_runner(*, sim: ClientSimulator, chunk: int, spec,
                      on_trace=None):
    """A *fresh* jit wrapper around :func:`_advance_body` — the chunked
    twin of :func:`make_group_runner`.

    Each runner owns its jit cache, so the serve layer's
    :class:`repro.serve.ExecutableCache` can memoize one per
    (structure, chunk length, config) and genuinely release its compiled
    executables on eviction; ``on_trace`` counts (re)traces the same
    way. A warm resume — the same structure advancing through the same
    chunk length — is a pure cache hit: zero new compiles.
    """

    def _runner(carry, scheduler, energy, faults, active, p):
        if on_trace is not None:
            on_trace()
        return _advance_body(carry, scheduler, energy, faults, active, p,
                             sim=sim, num_steps=chunk, spec=spec)

    return jax.jit(_runner)


def study_fingerprint(scenarios, num_steps, seed_list, params0) -> str:
    """Content hash binding a checkpoint directory to one exact study:
    canonical scenario specs + horizon + seeds + initial-parameter bytes.
    Resume refuses a directory whose manifest fingerprint differs. The
    serve layer keys per-dispatch-group checkpoint subdirectories on
    this same hash, so a restarted service lands on the directory its
    predecessor was writing."""
    h = hashlib.sha256()
    for sc in scenarios:
        d = dataclasses.asdict(sc)
        if d.get("taus") is not None:
            d["taus"] = np.asarray(d["taus"]).tolist()
        h.update(json.dumps(d, sort_keys=True, default=repr).encode())
    h.update(json.dumps({"num_steps": int(num_steps),
                         "seeds": [int(s) for s in seed_list]}).encode())
    for leaf in jax.tree_util.tree_leaves(params0):
        arr = np.asarray(leaf)
        h.update(str((arr.shape, arr.dtype.name)).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    return h.hexdigest()


def _history_template(n_scen, n_seeds, t, n_cap):
    """Shape/dtype template of an (S, R, t) SimHistory chunk as saved in
    resumable checkpoints (see :meth:`ClientSimulator._history`)."""
    return SimHistory(
        loss=jax.ShapeDtypeStruct((n_scen, n_seeds, t), jnp.float32),
        participation=jax.ShapeDtypeStruct((n_scen, n_seeds, t, n_cap),
                                           jnp.float32),
        weight_sum=jax.ShapeDtypeStruct((n_scen, n_seeds, t), jnp.float32),
        finite=jax.ShapeDtypeStruct((n_scen, n_seeds, t), jnp.bool_))


def _pad_halted_history(history, num_steps: int):
    """Extend a halted group's history to the full horizon: NaN metrics,
    ``finite=False`` — the quarantine tail (DESIGN.md §10)."""
    done = int(np.asarray(history.loss).shape[2])
    pad = num_steps - done
    if pad <= 0:
        return history

    def ext(x, value):
        shape = x.shape[:2] + (pad,) + x.shape[3:]
        return np.concatenate(
            [np.asarray(x), np.full(shape, value, np.asarray(x).dtype)],
            axis=2)

    return SimHistory(loss=ext(history.loss, np.nan),
                      participation=ext(history.participation, np.nan),
                      weight_sum=ext(history.weight_sum, np.nan),
                      finite=ext(history.finite, False))


def _advance_resumable_group(
    grp: StructureGroup, *, gid: str, sim: ClientSimulator, spec, params0,
    keys, seed_list, num_steps: int, checkpoint_every: int,
    checkpoint_dir: str, keep: int, manifest: dict, manifest_path: str,
    halt_on_divergence: bool, executable_cache=None, progress=None,
) -> CellResult:
    """Advance ONE structure group to the horizon, checkpointed.

    The factored inner loop of :func:`execute_cells_resumable`: restore
    the group's newest complete checkpoint (or init fresh), advance in
    ``checkpoint_every``-step chunks, and write ``{carry, history}``
    plus the study manifest after every chunk. ``executable_cache``
    routes each chunk advance through a memoized
    :func:`make_chunk_runner` (warm resumes are zero-compile);
    ``progress(gid, step, num_steps)`` fires once after restore/init and
    once per completed chunk, which is how the serve layer reports
    per-chunk dispatch progress.
    """
    from repro.checkpoint import CheckpointManager, latest_step, \
        write_json_atomic
    from repro.core import aggregation

    n_cap = int(sim.p.shape[0])
    mgr = CheckpointManager(os.path.join(checkpoint_dir, gid), keep=keep)
    carry_tpl = jax.eval_shape(
        partial(_init_group, sim=sim, spec=spec),
        grp.scheduler, grp.energy, grp.faults, keys, params0)
    step = latest_step(mgr.directory)
    halted = manifest["groups"][gid]["halted"]
    if step is None:
        step = 0
        halted = False
        carry = _init_group(grp.scheduler, grp.energy, grp.faults, keys,
                            params0, sim=sim, spec=spec)
        history = None
    else:
        tpl = {"carry": carry_tpl,
               "history": _history_template(len(grp.members), len(seed_list),
                                            step, n_cap)}
        state, step = mgr.restore(tpl, step)
        carry, history = state["carry"], state["history"]
    if progress is not None:
        progress(gid, step, num_steps)

    def save_state(step, carry, history, halted):
        mgr.save(step, {"carry": carry, "history": history})
        manifest["groups"][gid]["step"] = step
        manifest["groups"][gid]["halted"] = bool(halted)
        write_json_atomic(manifest_path, manifest)

    while step < num_steps and not halted:
        chunk = min(checkpoint_every, num_steps - step)
        if executable_cache is not None:
            runner = executable_cache.chunk_runner(
                (grp.key, grp.ragged, chunk), sim=sim, chunk=chunk, spec=spec)
            carry, hist = runner(carry, grp.scheduler, grp.energy, grp.faults,
                                 grp.active, grp.p)
        else:
            carry, hist = _advance_group(
                carry, grp.scheduler, grp.energy, grp.faults, grp.active,
                grp.p, sim=sim, num_steps=chunk, spec=spec)
        hist = jax.tree_util.tree_map(np.asarray, hist)
        history = hist if history is None else jax.tree_util.tree_map(
            lambda a, b: np.concatenate([a, b], axis=2), history, hist)
        step += chunk
        if halt_on_divergence and not np.asarray(
                history.finite[..., -1]).any():
            halted = True
        save_state(step, carry, history, halted)
        if progress is not None:
            progress(gid, step, num_steps)

    if history is None:  # num_steps == 0 degenerate study
        history = jax.tree_util.tree_map(
            lambda s: np.zeros(s.shape, s.dtype),
            _history_template(len(grp.members), len(seed_list), 0, n_cap))
    if halted:
        history = _pad_halted_history(history, num_steps)

    if spec is None:
        params = carry.params
    else:
        unravel = lambda q: aggregation.unravel_pytree(q, spec)  # noqa: E731
        params = jax.vmap(jax.vmap(unravel))(jnp.asarray(carry.params))
    return CellResult(params=params,
                      history=SimHistory(*map(jnp.asarray, history)),
                      evals=None)


def execute_cells_resumable(
    scenarios: Sequence[Scenario],
    *,
    sim: ClientSimulator,
    params0,
    num_steps: int,
    seeds: int | Sequence[int] = 8,
    checkpoint_dir: str,
    checkpoint_every: int = 0,
    keep: int = 3,
    halt_on_divergence: bool = False,
    executable_cache=None,
    progress=None,
) -> dict[str, CellResult]:
    """Preemption-safe :func:`execute_cells`: chunked scans + checkpoints.

    Execution proceeds structure group by structure group (same grouping
    as the batched path — :func:`resolve_structure_groups`), each group
    advancing in ``checkpoint_every``-step chunks
    (:func:`_advance_resumable_group`); after every chunk the group's
    ``{carry, history}`` pytree is written atomically under
    ``checkpoint_dir/<gid>/step_<t>.npz`` and the study manifest
    (``manifest.json``) is rewritten. Because each chunk is a pure
    function of the carry, a run killed at *any* point — including
    mid-write, by ``kill -9`` — resumes from the directory and produces
    results **bitwise identical** to the uninterrupted run: completed
    groups restore their final checkpoint without re-execution, the
    in-flight group restores its newest complete checkpoint and replays
    only the tail.

    The manifest binds the directory to one exact study via
    :func:`study_fingerprint` (scenario specs + horizon + seeds +
    params0 bytes); resuming with anything changed raises. Layout::

        {"format": "study-manifest/v1", "fingerprint": "<sha256>",
         "num_steps": T, "checkpoint_every": K,
         "groups": {"g000": {"members": [...], "step": t,
                             "halted": false}, ...}}

    ``halt_on_divergence=True`` stops advancing a group once **every**
    (scenario, seed) lane has gone non-finite (divergence is absorbing);
    the unrun tail is reported as NaN metrics with ``finite=False``.
    Eval hooks and meshes are not supported on this path — run those
    studies unchunked.

    ``executable_cache`` (DESIGN.md §12) memoizes one fresh
    :func:`make_chunk_runner` jit wrapper per (structure, chunk length)
    — the serve layer binds its keyed :class:`repro.serve.
    ExecutableCache` here so repeat resumable traffic, including a warm
    resume after an interruption, adds zero new compiles.
    ``progress(gid, step, num_steps)`` reports per-chunk advancement.
    """
    from repro.checkpoint import write_json_atomic

    scenarios = list(scenarios)
    del _LAST_DOWNGRADES[:]  # no ladder here, but keep the report current
    seed_list, keys = _seed_keys(seeds)
    num_steps = int(num_steps)
    if checkpoint_every <= 0:
        checkpoint_every = num_steps

    names, n_cap, groups = resolve_structure_groups(scenarios, sim=sim)
    spec = sim.flat_spec(params0)
    gids = [f"g{g:03d}" for g in range(len(groups))]

    manifest_path = os.path.join(checkpoint_dir, "manifest.json")
    fingerprint = study_fingerprint(scenarios, num_steps, seed_list, params0)
    manifest = {
        "format": MANIFEST_FORMAT,
        "fingerprint": fingerprint,
        "num_steps": num_steps,
        "checkpoint_every": int(checkpoint_every),
        "groups": {gid: {"members": [names[i] for i in grp.members],
                         "step": 0, "halted": False}
                   for gid, grp in zip(gids, groups)},
    }
    if os.path.exists(manifest_path):
        with open(manifest_path) as f:
            prev = json.load(f)
        if prev.get("format") != MANIFEST_FORMAT:
            raise ValueError(
                f"{manifest_path}: unknown manifest format "
                f"{prev.get('format')!r} (want {MANIFEST_FORMAT})")
        if prev.get("fingerprint") != fingerprint:
            raise ValueError(
                f"{manifest_path} belongs to a different study "
                f"(fingerprint mismatch) — refusing to resume; use a "
                f"fresh checkpoint_dir or delete the stale one")
        for gid in gids:
            got = prev["groups"].get(gid, {})
            manifest["groups"][gid]["halted"] = bool(got.get("halted", False))
    else:
        write_json_atomic(manifest_path, manifest)

    results: list[CellResult | None] = [None] * len(scenarios)
    for gid, grp in zip(gids, groups):
        out = _advance_resumable_group(
            grp, gid=gid, sim=sim, spec=spec, params0=params0, keys=keys,
            seed_list=seed_list, num_steps=num_steps,
            checkpoint_every=checkpoint_every, checkpoint_dir=checkpoint_dir,
            keep=keep, manifest=manifest, manifest_path=manifest_path,
            halt_on_divergence=halt_on_divergence,
            executable_cache=executable_cache, progress=progress)
        for j, idx in enumerate(grp.members):
            cell = jax.tree_util.tree_map(lambda x: x[j], out)
            cell = _crop_cell(cell, scenarios[idx].n_clients, n_cap)
            results[idx] = _attach_divergence(cell)
    return dict(zip(names, results))


def grid_summary(results: dict[str, CellResult], reducer=None) -> dict[str, dict]:
    """Per-scenario NaN-aware mean±std over the seed axis of a metric.

    ``reducer(cell) -> (R,)`` extracts one scalar per seed; default is
    the mean loss over the final 10% of steps. Diverged seeds (NaN/inf)
    are excluded from mean/std and counted in ``n_nan``
    (:func:`repro.experiments.results.seed_stats` — the same reduction
    backing :meth:`GridResult.reduce`).
    """
    from repro.experiments import results as results_mod

    reducer = results_mod.default_metric if reducer is None else reducer
    return {name: results_mod.seed_stats(reducer(cell))
            for name, cell in results.items()}
