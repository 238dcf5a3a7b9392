"""Plain reference of one simulated federated study (arXiv:2102.05639 §II-§V).

One answer is one (scheduler, population, seed) run of ``T`` rounds over
a population of ``n`` clients laid out in ``n_cap`` rows. Round ``t``:

* energy: periodic arrivals, client ``i`` harvests at ``t % tau_i == 0``
  for ``t`` below the horizon ``T + 1``; the gap at ``t`` is the distance
  from the last arrival at or before ``t`` to the next one (the horizon
  if none comes);
* scheduler: Algorithm 1 books, on arrival, an appointment ``J`` rounds
  ahead with ``J = floor(u * gap)``, ``u`` uniform, and participates then
  with scale ``gap``; Benchmark 1 participates on arrival with scale 1;
  Benchmark 2 banks energy in a unit battery and all clients step
  together once every battery is full; the oracle always participates;
* weights ``w_i = p_i * mask_i * scale_i`` with ``p_i`` the client's share
  of the active population's data;
* each client's gradient is that of its mean loss over ``batch`` examples
  of its own shard, drawn uniformly with replacement;
* SGD: ``theta <- theta - lr * sum_i w_i g_i``.

Randomness follows the simulator's documented stream: the run key is
``PRNGKey(seed)`` split in three (scheduler, energy, run); each round
splits the run key in four (next, arrivals, scheduler, gradient). Client
``i``'s uniform draw is ``uniform(fold_in(k_scheduler, i))``; the round's
batch rows are ``randint(k_gradient, (n_cap, batch), 0, shard_size)``.

The model enters as a module with ``loss(params, x, y, precision)`` (the
configuration's plain reference). Shards are ``(n_cap, shard, ...)`` of
any trailing shape. ``dtype`` is the type the reference computes in:
float32 at ``highest`` precision for the reference, bfloat16 at default
precision for the control; floating inputs are cast to it, integer ones
(token ids) reach the model as they are.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SCHEDULERS = ("alg1", "benchmark1", "benchmark2", "oracle")


@partial(jax.jit, static_argnames=("rounds",))
def round_keys(seed_key, rounds: int):
    """(rounds, 2) scheduler keys and (rounds, 2) gradient keys."""
    _, _, run = jax.random.split(seed_key, 3)

    def step(key, _):
        nxt, _arr, k_sched, k_grad = jax.random.split(key, 4)
        return nxt, (k_sched, k_grad)

    _, (k_sched, k_grad) = jax.lax.scan(step, run, None, length=rounds)
    return k_sched, k_grad


@partial(jax.jit, static_argnames=("n_cap",))
def client_uniforms(k_sched, n_cap: int):
    """(rounds, n_cap) float32 uniforms, one per client and round."""
    def one(key):
        return jax.vmap(lambda i: jax.random.uniform(
            jax.random.fold_in(key, i), ()))(jnp.arange(n_cap))
    return jax.vmap(one)(k_sched)


def periodic_energy(taus, n_cap: int, horizon: int):
    """(horizon, n_cap) arrivals and gaps; rows past ``len(taus)`` never
    harvest."""
    t = np.arange(horizon)
    energy = np.zeros((horizon, n_cap), np.float32)
    gap = np.zeros((horizon, n_cap), np.float32)
    for i, tau in enumerate(taus):
        last = (t // tau) * tau
        energy[:, i] = t % tau == 0
        gap[:, i] = np.minimum(last + tau, horizon) - last
    return energy, gap


def schedule(name: str, taus, n_cap: int, rounds: int, uniforms):
    """(rounds, n_cap) participation masks and scales of one run."""
    n = len(taus)
    active = np.arange(n_cap) < n
    energy, gap = periodic_energy(taus, n_cap, rounds + 1)
    masks = np.zeros((rounds, n_cap), np.float32)
    scales = np.ones((rounds, n_cap), np.float32)
    appt_time = np.full(n_cap, -1)
    appt_scale = np.zeros(n_cap, np.float32)
    battery = np.zeros(n_cap, np.float32)
    for t in range(rounds):
        arrived = energy[t] > 0
        if name == "alg1":
            g = np.maximum(gap[t], np.float32(1))
            j = np.minimum(np.floor(uniforms[t] * g), g - 1).astype(int)
            appt_time = np.where(arrived, t + j, appt_time)
            appt_scale = np.where(arrived, g, appt_scale)
            masks[t] = appt_time == t
            scales[t] = appt_scale
        elif name == "benchmark1":
            masks[t] = arrived
        elif name == "benchmark2":
            battery = np.minimum(battery + energy[t], 1)
            masks[t] = active if battery[active].min() >= 1 else 0
            battery = battery - masks[t]
        elif name == "oracle":
            masks[t] = active
        else:
            raise ValueError(f"no reference for scheduler {name!r}; "
                             f"have {SCHEDULERS}")
    return masks, scales


def _floating_as(x, dtype):
    """``x`` in ``dtype`` where it is floating; integers stay as they are."""
    return x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x


def data_weights(shard_sizes, n: int, n_cap: int):
    """(n_cap,) p_i = D_i / D over the ``n`` active clients, 0 beyond."""
    sizes = np.asarray(shard_sizes[:n], np.float64)
    p = np.zeros(n_cap, np.float64)
    p[:n] = sizes / sizes.sum()
    return p.astype(np.float32)


@partial(jax.jit, static_argnames=("model", "batch", "precision", "keep"))
def _weighted_grad_block(params, shards_x, shards_y, rows, weights, k_grad,
                         *, model, batch: int, precision, keep=None):
    """sum_i w_i g_i over one block of client ``rows`` (padded rows carry
    weight 0), with the batch drawn for the whole population; ``keep``
    uses only the first ``keep`` examples of each client's batch."""
    n_cap, shard = shards_y.shape[:2]
    idx = jax.random.randint(k_grad, (n_cap, batch), 0, shard)[rows, :keep]
    x = jax.vmap(lambda r, ix: shards_x[r][ix])(rows, idx)
    y = jax.vmap(lambda r, ix: shards_y[r][ix])(rows, idx)
    x = _floating_as(x, weights.dtype)
    grads = jax.vmap(jax.grad(
        lambda p, xb, yb: model.loss(p, xb, yb, precision)),
        in_axes=(None, 0, 0))(params, x, y)
    return jax.tree_util.tree_map(
        lambda g: jnp.tensordot(weights, g, axes=1, precision=precision),
        grads)


@partial(jax.jit, static_argnames=("model", "precision"))
def _held_out_loss(params, x, y, *, model, precision):
    return model.loss(params, _floating_as(x, jax.tree_util.tree_leaves(
        params)[0].dtype), y, precision).astype(jnp.float32)


def follow(model, params0, shards_x, shards_y, loss_x, loss_y, weights,
           k_grad, *, batch: int, lr: float, dtype=jnp.float32,
           block: int | None = None, keep: int | None = None,
           loss_rounds: int = 0):
    """Follow a run of ``len(weights)`` rounds from ``params0``: the
    held-out losses after each of the first ``loss_rounds`` rounds
    (float64) and the final parameters (numpy, in ``dtype``).

    ``weights`` is (rounds, n_cap); client gradients are computed in
    blocks of ``block`` rows (default: all), skipping rows of weight 0.
    ``keep`` plants a fault for the control readings: each client's
    gradient over only the first ``keep`` examples of its batch.
    """
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    n_cap = shards_y.shape[0]
    block = block or n_cap
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params0)
    lr = jnp.asarray(lr, dtype)
    losses = []
    for t, (w_t, key) in enumerate(zip(np.asarray(weights), k_grad)):
        live = np.flatnonzero(w_t)
        agg = jax.tree_util.tree_map(jnp.zeros_like, params)
        for lo in range(0, len(live), block):
            rows = live[lo:lo + block]
            pad = block - len(rows)
            w = np.concatenate([w_t[rows], np.zeros(pad, np.float32)])
            rows = np.concatenate([rows, np.zeros(pad, rows.dtype)])
            part = _weighted_grad_block(
                params, shards_x, shards_y, jnp.asarray(rows, jnp.int32),
                jnp.asarray(w, dtype), key, model=model, batch=batch,
                precision=precision, keep=keep)
            agg = jax.tree_util.tree_map(jnp.add, agg, part)
        params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, agg)
        if t < loss_rounds:
            losses.append(_held_out_loss(params, loss_x, loss_y,
                                         model=model, precision=precision))
    return (np.asarray(jax.device_get(losses), np.float64),
            jax.tree_util.tree_map(np.asarray, params))
