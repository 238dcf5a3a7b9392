"""EnergyAwareTrainer — couples energy process, scheduler and SGD.

Two execution modes cover the paper-scale and framework-scale regimes:

1. :class:`ClientSimulator` — the paper's setting verbatim: N clients,
   per-client stochastic gradients (vmapped), server aggregation with
   ω_i = p_i·mask_i·scale_i. Whole loop runs under ``jax.lax.scan`` so a
   1000-iteration × 40-client run is one XLA computation.

2. :func:`build_energy_train_step` — the SPMD path used by
   ``repro.launch.train``: the global batch is partitioned into client
   slots; each example's loss is multiplied by its client coefficient
   (``repro.core.aggregation.per_example_coefficients``) so a *single*
   backward pass + the ordinary data-parallel all-reduce realizes the
   paper's eq. (11/12) with zero extra collective traffic.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import tracing
from repro.core import aggregation
from repro.core.energy import client_shard
from repro.core.scheduling import Decision
from repro.optim import Optimizer, apply_updates


class SimCarry(NamedTuple):
    params: Any
    opt_state: Any
    sched_state: Any
    energy_state: Any
    key: jax.Array
    t: jax.Array
    fault_state: Any = ()     # fault-component state ((): no/stateless faults)


class SimHistory(NamedTuple):
    loss: jax.Array           # (T,) global loss (if loss_fn given, else 0)
    participation: jax.Array  # (T, N) masks
    weight_sum: jax.Array     # (T,) Σ_i ω_i (≈1 in expectation for unbiased)
    finite: jax.Array = None  # (T,) bool — params finite after the step
    #                           (the per-step isfinite reduction behind
    #                           non-finite quarantine, DESIGN.md §10)


class ClientSimulator:
    """Paper-faithful N-client distributed-SGD simulator.

    Parameters
    ----------
    grads_fn : (params, key, t) -> (N,)-stacked gradient pytree.
        Owns data sampling (eq. 4); must return *local* gradients g_i.
    scheduler, energy : repro.core.scheduling / repro.core.energy pytrees.
        Optional at construction — every method also accepts them as
        explicit (traced) arguments, so a single simulator can execute a
        whole leaf-stacked family of scenarios under ``vmap``
        (:func:`repro.experiments.run_grid`). ``run``/``step`` also
        accept per-run ``p`` and ``active_mask`` overrides — the
        ragged-population mechanism (DESIGN.md §7): components padded to
        a common width run with ``active_mask`` marking the rows that
        exist; inactive rows contribute exactly zero gradient and zero
        scheduler probability mass, bit-for-bit matching the natural-N
        run.
    p : (N,) data weights p_i = D_i / D.
    optimizer : repro.optim.Optimizer applied to the aggregated update.
        For exact paper semantics use ``sgd(eta)``.
    loss_fn : optional (params) -> scalar global loss, logged per step.
    use_kernel : route aggregation through the Pallas kernel path. The
        kernel serves gradients that arrive as one flat buffer: a single
        ``(N, ...)`` array, or the flat rows that faults or the ``psum``
        and ``fused`` client reductions act on. A multi-leaf gradient
        tree in a flat-carry, fault-free step, unsharded or under
        ``gather``, is reduced leaf by leaf whatever this says
        (DESIGN.md §5): raveling it into ``(N, P)`` for the kernel would
        cost more than the kernel's one read.

    Under an active client-sharding context (DESIGN.md §8 — entered by
    the placement layer's ``run_client_sharded`` / ``clients``-mesh grid
    paths, never directly by users) the simulator runs with per-client
    state and the gradient buffer device-local and the aggregation
    reduced across the client mesh axis; requires flat-carry execution.

    flat : run the scan loop in flat parameter space (DESIGN.md §5):
        params and optimizer state live as single ``(P,)`` buffers in the
        scan carry, aggregation is one kernel/matvec per step, and the
        pytree is materialized only at the grads_fn/loss_fn/eval_fn
        boundaries. ``None`` (default) enables it whenever every param
        leaf shares one dtype; ``False`` restores full legacy semantics
        (per-leaf carry *and* per-leaf aggregation in leaf dtype);
        ``True`` raises on mixed-dtype params.
    """

    def __init__(self, *, grads_fn, p, optimizer: Optimizer,
                 scheduler=None, energy=None, faults=None,
                 loss_fn=None, use_kernel: bool = False,
                 flat: bool | None = None):
        self.grads_fn = grads_fn
        self.scheduler = scheduler
        self.energy = energy
        self.faults = faults
        self.p = jnp.asarray(p, jnp.float32)
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.use_kernel = use_kernel
        self.flat = flat

    def _components(self, scheduler, energy):
        scheduler = self.scheduler if scheduler is None else scheduler
        energy = self.energy if energy is None else energy
        if scheduler is None or energy is None:
            raise ValueError(
                "scheduler/energy must be given either at construction or "
                "as arguments to init/step/run")
        return scheduler, energy

    def _fault(self, faults):
        """Constructor fault component unless overridden (None: no faults)."""
        return self.faults if faults is None else faults

    def _flat_spec(self, params):
        """RavelSpec for flat-carry execution, or None for the legacy path."""
        if self.flat is False:
            return None
        try:
            return aggregation.ravel_spec(params)
        except ValueError:
            if self.flat:
                raise
            return None

    def flat_spec(self, params):
        """Public :class:`~repro.core.aggregation.RavelSpec` accessor —
        the spec :meth:`run` executes under for these params (None when
        the legacy per-leaf path would be taken). Checkpoint drivers pass
        it to :meth:`init` / :meth:`run_carry` so a saved flat
        :class:`SimCarry` resumes in the same layout."""
        return self._flat_spec(params)

    def init(self, key, params, *, scheduler=None, energy=None,
             faults=None, spec=None) -> SimCarry:
        """Build the scan carry; with ``spec`` params/opt_state are flat."""
        scheduler, energy = self._components(scheduler, energy)
        faults = self._fault(faults)
        if faults is not None and spec is None:
            raise ValueError(
                "fault injection (repro.core.faults) requires flat-carry "
                "execution: uniform-dtype params and flat != False "
                "(DESIGN.md §10)")
        if spec is not None:
            leaves = jax.tree_util.tree_leaves(params)
            params = aggregation.ravel_pytree(params, spec)
            if len(leaves) == 1 and params is leaves[0]:
                # Single-leaf ravel is a no-op reshape returning the
                # caller's array itself; the carry must own its storage
                # because run_carry donates it (DESIGN.md §9).
                params = jnp.array(params, copy=True)
        k_sched, k_energy, k_run = jax.random.split(key, 3)
        fault_state = ()
        if faults is not None:
            # Derived from k_run by domain-separated fold_in — never by
            # widening the split arity — so every fault-free RNG stream
            # is bitwise unchanged by the presence of a fault component.
            from repro.core.faults import FAULT_SALT

            fault_state = faults.init(
                jax.random.fold_in(k_run, FAULT_SALT),
                int(self.p.shape[0]), int(spec.total))
        return SimCarry(
            params=params,
            opt_state=self.optimizer.init(params),
            sched_state=scheduler.init(k_sched),
            energy_state=energy.init(k_energy),
            key=k_run,
            t=jnp.zeros((), jnp.int32),
            fault_state=fault_state,
        )

    def step(self, carry: SimCarry, scheduler=None, energy=None, *,
             p=None, active_mask=None, faults=None) -> tuple[SimCarry, dict]:
        """One server round on a pytree carry (public single-step API)."""
        return self._step(carry, scheduler, energy, None, p, active_mask,
                          faults)

    def _step(self, carry: SimCarry, scheduler, energy, spec,
              p=None, active_mask=None, faults=None) -> tuple[SimCarry, dict]:
        """Shared step body; ``spec`` non-None means carry.params is the
        raveled ``(P,)`` vector and aggregation stays in flat space.
        ``p`` overrides the constructor weights (ragged cells carry
        their own zero-padded, active-renormalized p); ``active_mask``
        is the (N,) 0/1 existing-client mask; ``faults`` an optional
        fault-injection component (:mod:`repro.core.faults`) applied to
        the flat gradient buffer before aggregation."""
        scheduler, energy = self._components(scheduler, energy)
        faults = self._fault(faults)
        shard = client_shard()
        if shard is not None and spec is None:
            raise ValueError(
                "client-axis sharding (DESIGN.md §8) requires flat-carry "
                "execution: uniform-dtype params and flat != False")
        if faults is not None:
            if spec is None:
                raise ValueError(
                    "fault injection (repro.core.faults) requires "
                    "flat-carry execution: uniform-dtype params and "
                    "flat != False (DESIGN.md §10)")
            if shard is not None:
                raise ValueError(
                    "fault injection is not supported under a clients "
                    "mesh axis (client-sharded fault state is future "
                    "work; DESIGN.md §10) — drop the clients axis or "
                    "the fault component")
        p = self.p if p is None else p
        key, k_arr, k_sched, k_grad = jax.random.split(carry.key, 4)
        # The step's phases are named in the ops' metadata (sim.schedule,
        # sim.grads, sim.update, sim.eval); the scopes change no op.
        with jax.named_scope("sim.schedule"):
            energy_state, arr = energy.arrivals(carry.energy_state, carry.t,
                                                k_arr)
            sched_state, dec = scheduler.step(carry.sched_state, carry.t,
                                              k_sched, arr, active=active_mask)
            weights = aggregation.client_weights(p, dec)
            if active_mask is not None:
                # Defensive exactness: zero weight for rows that don't
                # exist even if a custom scheduler leaked probability
                # mass to them (×1 on active rows — bit-exact).
                weights = weights * active_mask
        wsum = None
        agg = params = opt_state = None
        fault_state = carry.fault_state
        row_mask = active_mask
        fusable = getattr(self.optimizer, "kind", "") == "sgd"
        if spec is not None:
            with jax.named_scope("sim.grads"):
                params_tree = aggregation.unravel_pytree(carry.params, spec)
                n_rows = int(self.p.shape[0])
                if shard is None:
                    stacked = self.grads_fn(params_tree, k_grad, carry.t)
                else:
                    stacked, n_rows = aggregation.shard_client_grads(
                        self.grads_fn, params_tree, k_grad, carry.t,
                        n_rows, shard)
                # Faults and the psum/fused reductions act on flat rows;
                # otherwise a gradient tree is reduced where it lies
                # (g None) and no (N, P) copy of it is written.
                leafwise = (faults is None and (
                    shard is None or aggregation.parse_reduction(
                        shard.reduction)[0] == "gather")
                    and aggregation.reduces_leafwise(stacked, spec))
                g = (None if leafwise else aggregation.flatten_client_grads(
                    stacked, spec, n_rows))
            if faults is not None:
                # Delivery faults transform the flat rows and/or return a
                # keep mask; keep composes into the active-row select so
                # a dropped row is an exact zero through the masked
                # kernels even when its payload is non-finite, and
                # zero-weighting keeps weight_sum the delivered mass.
                from repro.core.faults import FAULT_SALT

                k_fault = jax.random.fold_in(k_grad, FAULT_SALT)
                fault_state, g, keep = faults.apply(
                    carry.fault_state, carry.t, k_fault, g)
                if keep is not None:
                    weights = weights * keep
                    row_mask = aggregation.compose_masks(active_mask, keep)
            with jax.named_scope("sim.update"):
                if g is None:
                    tracing.count("sim.update_leafwise")
                    if shard is not None:
                        # gather: every shard replays the unsharded
                        # reduction over the reassembled rows, bitwise
                        # the single-device step.
                        stacked, weights, row_mask = \
                            aggregation.gather_client_rows(
                                stacked, weights, row_mask, shard.axis_name)
                    agg = aggregation.ravel_pytree(
                        aggregation.aggregate_client_grads(
                            stacked, weights, row_mask), spec)
                    if fusable:
                        # Plain sgd steps through the fused op on every
                        # flat-carry path (the benchmark's fault check
                        # replaces it): the aggregate as a one-client
                        # stack of unit weight, whose jnp branch is the
                        # kernel's params − η·agg.
                        params, opt_state, _ = \
                            aggregation.fused_flat_sgd_update(
                                agg[None, :], jnp.ones((1,), jnp.float32),
                                carry.params, carry.opt_state,
                                self.optimizer)
                elif shard is not None:
                    mode, wire = aggregation.parse_reduction(shard.reduction)
                    if mode == "fused":
                        if not fusable:
                            raise ValueError(
                                "reduction 'fused' bundles the SGD "
                                "parameter update into the reduction "
                                "kernel and needs a plain sgd() optimizer "
                                "(kind='sgd'); use 'psum' for "
                                "stateful/clipped optimizers")
                        params, opt_state, wsum = \
                            aggregation.fused_flat_sgd_update(
                                g, weights, carry.params, carry.opt_state,
                                self.optimizer, mask=row_mask,
                                use_kernel=self.use_kernel, shard=shard,
                                wire_dtype=wire)
                    else:
                        agg, wsum = aggregation.reduce_flat_client_sharded(
                            g, weights, axis_name=shard.axis_name,
                            reduction=shard.reduction,
                            use_kernel=self.use_kernel, mask=row_mask)
                elif self.use_kernel and fusable:
                    # Unsharded fused fast path: identical f32 op sequence
                    # to reduce → −η·agg → add, collapsed into one Pallas
                    # launch.
                    params, opt_state, _ = aggregation.fused_flat_sgd_update(
                        g, weights, carry.params, carry.opt_state,
                        self.optimizer, mask=row_mask, use_kernel=True)
                else:
                    agg = aggregation.reduce_flat(g, weights,
                                                  use_kernel=self.use_kernel,
                                                  mask=row_mask)
        elif self.flat is False:
            # Full legacy semantics: per-leaf reductions (and per-leaf
            # kernel launches), leaf dtypes untouched — the escape hatch
            # and the reference the flat paths are tested against.
            with jax.named_scope("sim.grads"):
                stacked = self.grads_fn(carry.params, k_grad, carry.t)
            with jax.named_scope("sim.update"):
                agg = (aggregation.aggregate_client_grads_kernel_per_leaf(
                           stacked, weights, active_mask) if self.use_kernel
                       else aggregation.aggregate_client_grads(
                           stacked, weights, active_mask))
        else:
            with jax.named_scope("sim.grads"):
                stacked = self.grads_fn(carry.params, k_grad, carry.t)
            with jax.named_scope("sim.update"):
                agg = aggregation.aggregate_client_grads_flat(
                    stacked, weights, use_kernel=self.use_kernel,
                    mask=active_mask)
        with jax.named_scope("sim.update"):
            if params is None:
                updates, opt_state = self.optimizer.update(
                    agg, carry.opt_state, carry.params)
                params = apply_updates(carry.params, updates)
        with jax.named_scope("sim.eval"):
            loss_params = (aggregation.unravel_pytree(params, spec)
                           if spec is not None else params)
            loss = (self.loss_fn(loss_params) if self.loss_fn is not None
                    else jnp.zeros((), jnp.float32))
        with jax.named_scope("sim.update"):
            if spec is not None:
                finite = jnp.all(jnp.isfinite(params))
            else:
                finite = jnp.array(True)
                for leaf in jax.tree_util.tree_leaves(params):
                    finite = jnp.logical_and(finite,
                                             jnp.all(jnp.isfinite(leaf)))
        out = {
            "loss": loss,
            "participation": dec.mask,
            "weight_sum": jnp.sum(weights) if wsum is None else wsum,
            "finite": finite,
        }
        new_carry = SimCarry(params=params, opt_state=opt_state,
                             sched_state=sched_state, energy_state=energy_state,
                             key=key, t=carry.t + 1,
                             fault_state=fault_state)
        return new_carry, out

    def run(self, key, params, num_steps: int, *, scheduler=None, energy=None,
            faults=None, p=None, active_mask=None, eval_fn=None,
            eval_every: int = 0):
        """Run the whole loop as one (or a few) ``lax.scan`` computations.

        ``p`` / ``active_mask`` override the constructor weights and mark
        the existing-client rows of a padded (ragged) population — see
        the class docstring and DESIGN.md §7.

        Without ``eval_fn``: returns ``(final_params, SimHistory)``.

        With ``eval_fn`` (params -> metric pytree): the scan runs in
        ``num_steps // eval_every`` chunks, evaluating after each chunk,
        and returns ``(final_params, SimHistory, evals)`` where every
        ``evals`` leaf has leading axis ``num_steps // eval_every``. This
        keeps evaluation *inside* the compiled computation so grid
        engines can vmap it (DESIGN.md §1).

        When the parameter pytree has a single leaf dtype (``flat``
        mode, the default), the scan carry holds params and optimizer
        state as single flat buffers and never round-trips optimizer
        state leaf-by-leaf. Per step, gradients that arrive flat take
        exactly one aggregation kernel/matvec over the whole ``(N, P)``
        buffer; a multi-leaf gradient tree is reduced leaf by leaf and
        only its ``(P,)`` aggregate raveled. The pytree view exists only
        at the grads_fn/loss_fn/eval_fn boundaries (cheap
        slices/reshapes XLA fuses away). The returned ``final_params``
        is always the original pytree structure.
        """
        scheduler, energy = self._components(scheduler, energy)
        faults = self._fault(faults)
        spec = self._flat_spec(params)
        carry = self.init(key, params, scheduler=scheduler, energy=energy,
                          faults=faults, spec=spec)

        def unflatten(p):
            return aggregation.unravel_pytree(p, spec) if spec is not None else p

        if eval_fn is None:
            carry, history = self.run_carry(
                carry, num_steps, scheduler=scheduler, energy=energy,
                faults=faults, p=p, active_mask=active_mask, spec=spec)
            return unflatten(carry.params), history

        if eval_every <= 0:
            eval_every = num_steps
        if num_steps % eval_every != 0:
            raise ValueError(
                f"num_steps={num_steps} must divide by eval_every={eval_every}")

        def body(c, _):
            return self._step(c, scheduler, energy, spec, p, active_mask,
                              faults)

        def chunk(c, _):
            c, outs = jax.lax.scan(body, c, None, length=eval_every)
            with jax.named_scope("sim.eval"):
                evals = eval_fn(unflatten(c.params))
            return c, (outs, evals)

        carry, (outs, evals) = jax.lax.scan(
            chunk, carry, None, length=num_steps // eval_every)
        outs = jax.tree_util.tree_map(
            lambda x: x.reshape((num_steps,) + x.shape[2:]), outs)
        return unflatten(carry.params), self._history(outs), evals

    def _scan_steps(self, carry: SimCarry, num_steps: int, scheduler, energy,
                    p, active_mask, spec, faults=None):
        def body(c, _):
            return self._step(c, scheduler, energy, spec, p, active_mask,
                              faults)

        return jax.lax.scan(body, carry, None, length=num_steps)

    def run_carry(self, carry: SimCarry, num_steps: int, *, scheduler=None,
                  energy=None, faults=None, p=None, active_mask=None,
                  spec=None, donate: bool = True
                  ) -> tuple[SimCarry, SimHistory]:
        """Advance an existing carry ``num_steps`` rounds as one scan.

        The checkpoint/resume entry point: a :class:`SimCarry` from
        :meth:`init` (or from a restored checkpoint — the carry is an
        ordinary pytree, so :func:`repro.checkpoint.save_pytree` /
        ``restore_pytree`` round-trip it) resumes bitwise-identically to
        the uninterrupted run, because the whole step stream is a pure
        function of the carry. ``spec`` must be the
        :meth:`flat_spec` of the original params when the carry is flat
        (the default execution mode), None for the legacy pytree carry.
        Returns the advanced carry (same layout) and the chunk's
        :class:`SimHistory`.

        When called at the top level (not under an enclosing trace) on a
        **flat** carry, the scan runs under a jit that **donates** the
        input carry: the flat ``(P,)`` params/opt-state buffers alias
        the output instead of holding two live copies of the largest
        state in the loop (DESIGN.md §9). The input ``carry`` is
        consumed — rebind the result, as every call site here already
        does; restored checkpoints stay valid because donation consumes
        the device buffer, not the file. ``donate=False`` opts out.
        Legacy pytree carries (``spec=None``) never donate — their
        params leaves are the caller's own arrays. Under an outer trace
        (vmap/jit of a caller) the scan inlines as before and donation
        is the caller's concern.
        """
        scheduler, energy = self._components(scheduler, energy)
        faults = self._fault(faults)
        if donate and spec is not None and not _any_tracer(
                (carry, scheduler, energy, faults, p, active_mask)):
            carry, outs = _run_carry_donated(
                carry, scheduler, energy, faults, p, active_mask,
                sim=self, num_steps=int(num_steps), spec=spec)
        else:
            carry, outs = self._scan_steps(carry, num_steps, scheduler,
                                           energy, p, active_mask, spec,
                                           faults)
        return carry, self._history(outs)

    @staticmethod
    def _history(outs) -> SimHistory:
        return SimHistory(loss=outs["loss"], participation=outs["participation"],
                          weight_sum=outs["weight_sum"],
                          finite=outs["finite"])


def _any_tracer(tree) -> bool:
    """True when any leaf of ``tree`` is a tracer — i.e. the caller is
    under an enclosing trace (vmap/jit/scan), not at the top level."""
    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(tree))


@functools.partial(jax.jit, static_argnames=("sim", "num_steps", "spec"),
                   donate_argnums=(0,))
def _run_carry_donated(carry, scheduler, energy, faults, p, active_mask, *,
                       sim: ClientSimulator, num_steps: int, spec):
    """Top-level jit of the :meth:`ClientSimulator.run_carry` scan with
    the carry donated — input params/opt-state buffers alias the outputs.
    ``sim`` is static (hashed by identity; its fields select the trace),
    so each simulator instance owns its compiled executable."""
    return sim._scan_steps(carry, num_steps, scheduler, energy, p,
                           active_mask, spec, faults)


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def build_energy_train_step(
    *,
    per_example_loss_fn: Callable[..., jax.Array],
    optimizer: Optimizer,
    n_clients: int,
    p: jax.Array | None = None,
    aux_loss_weight: float = 0.0,
    flat: bool = False,
    use_kernel: bool = False,
):
    """SPMD train step with the paper's weighting baked into the loss.

    per_example_loss_fn(params, batch) must return per-example losses of
    shape (B,) — or (B,), aux_scalar when the model carries an auxiliary
    loss (MoE load balance). ``batch`` must contain ``client_ids`` (B,)
    int32. The returned step:

        train_step(state, batch, mask, scale) -> (state, metrics)

    where (mask, scale) are the (N,) scheduler outputs for this step.
    The aux loss (router load-balance) is weighted by mean(coeff·N) so a
    masked client contributes nothing to router statistics either — see
    DESIGN.md §4 (MoE note).

    ``flat=True`` routes the gradient through the same RavelSpec-aware
    flat boundary as :class:`ClientSimulator` (DESIGN.md §5/§8): the
    loss-path gradient is raveled into one ``(P,)`` buffer, optimizer
    state lives flat, and the pytree view is rebuilt only at the
    ``TrainState.params`` boundary. Elementwise-optimizer numerics are
    bitwise unchanged. With a plain tagged ``sgd()`` optimizer the flat
    step further routes through :func:`repro.core.aggregation.
    fused_flat_sgd_update` — the whole reduce-and-update as one fused
    pass (a single Pallas launch when ``use_kernel``, DESIGN.md §9); the
    f32 op sequence is unchanged. Leave ``flat`` False (the default) for
    pjit-sharded training — per-leaf optimizer state follows the
    parameter PartitionSpecs (``repro.sharding.rules``), a single flat
    buffer cannot.
    """
    if p is None:
        p = jnp.full((n_clients,), 1.0 / n_clients, jnp.float32)
    p = jnp.asarray(p, jnp.float32)

    def loss_fn(params, batch, weights):
        out = per_example_loss_fn(params, batch)
        aux = jnp.zeros((), jnp.float32)
        if isinstance(out, tuple):
            losses, aux = out
        else:
            losses = out
        bsz = losses.shape[0]
        coeff = aggregation.per_example_coefficients(
            batch["client_ids"], weights, bsz // n_clients)
        total = jnp.sum(coeff * losses)
        if aux_loss_weight:
            # Scale aux by the mean client weight so the energy mask also
            # de-biases router statistics.
            total = total + aux_loss_weight * aux * jnp.sum(weights)
        # Unweighted mean loss for logging.
        return total, jnp.mean(losses)

    def train_step(state: TrainState, batch, mask, scale):
        weights = aggregation.client_weights(p, Decision(mask=mask, scale=scale))
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (total, mean_loss), grads = grad_fn(state.params, batch, weights)
        if flat:
            spec = aggregation.ravel_spec(state.params)
            gflat = aggregation.ravel_pytree(
                jax.tree_util.tree_map(lambda g: g.astype(spec.dtype), grads),
                spec)
            pflat = aggregation.ravel_pytree(state.params, spec)
            if getattr(optimizer, "kind", "") == "sgd":
                # The SPMD gradient is already reduced over examples, so
                # the fused op sees it as a one-client stack with unit
                # weight: one fused reduce-and-update pass (single Pallas
                # launch under use_kernel) replaces update+apply.
                pnew, opt_state, _ = aggregation.fused_flat_sgd_update(
                    gflat[None, :], jnp.ones((1,), jnp.float32), pflat,
                    state.opt_state, optimizer, use_kernel=use_kernel)
                params = aggregation.unravel_pytree(pnew, spec)
            else:
                updates, opt_state = optimizer.update(gflat, state.opt_state,
                                                      pflat)
                params = aggregation.unravel_pytree(pflat + updates, spec)
        else:
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = apply_updates(state.params, updates)
        metrics = {
            "weighted_loss": total,
            "loss": mean_loss,
            "active_clients": jnp.sum(mask),
            "weight_sum": jnp.sum(weights),
        }
        return TrainState(params=params, opt_state=opt_state, step=state.step + 1), metrics

    def init_state(params) -> TrainState:
        if flat:
            spec = aggregation.ravel_spec(params)
            opt_state = optimizer.init(aggregation.ravel_pytree(params, spec))
        else:
            opt_state = optimizer.init(params)
        return TrainState(params=params, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32))

    return init_state, train_step
