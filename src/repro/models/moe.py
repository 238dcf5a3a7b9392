"""Mixture-of-Experts FFN with top-k routing and capacity-based dispatch.

Dispatch is **scatter/gather based** (not the GShard one-hot-einsum): the
one-hot dispatch einsum costs O(K·T²/E·d) FLOPs which would poison the
roofline tables; scatter-add into an expert buffer is O(T·K·d).

The buffer layout is **hierarchical / shard-local** (EXPERIMENTS.md §Perf,
hillclimb 1): capacity slots are partitioned by data shard — token t on
data shard s can only occupy slots in shard s's slice, so the
position-in-expert cumsum runs per shard-row and the scatter writes stay
local to the data shard. The buffer is sharded (experts → "model",
capacity → ("pod","data")); without the capacity-axis sharding GSPMD
replicates the whole expert computation on every data shard (measured
4.06× FLOPs on a (4,2) mesh, ~16× at production), and without the
shard-local slot arithmetic it replicates the token buffers around the
scatter (measured 3.4 TB of all-gather per phi3.5 train step).

Capacity is enforced per (expert, data-shard) — the standard
expert-parallel semantics; overflowing tokens drop (combine weight 0,
residual passes through).

Covers both assigned MoE archs:
  * phi3.5-moe  — 16 experts, top-2, no shared expert
  * llama4-scout — 16 experts, top-1 + always-on shared expert
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import (
    activation,
    current_mesh,
    dense,
    dense_init,
    lecun_init,
    maybe_shard,
)


def init_moe(key, d_model, d_ff, n_experts, dtype, use_bias=False,
             shared_expert=False, shared_d_ff=None):
    ks = jax.random.split(key, 5)
    p = {
        "router": dense_init(ks[0], d_model, n_experts, jnp.float32),
        "w_gate": lecun_init(ks[1], (n_experts, d_model, d_ff), dtype, fan_in=d_model),
        "w_up": lecun_init(ks[2], (n_experts, d_model, d_ff), dtype, fan_in=d_model),
        "w_down": lecun_init(ks[3], (n_experts, d_ff, d_model), dtype, fan_in=d_ff),
    }
    if shared_expert:
        from repro.models.blocks import init_mlp  # local import to avoid cycle
        p["shared"] = init_mlp(ks[4], d_model, shared_d_ff or d_ff, dtype, use_bias)
    return p


def _data_shards(t: int) -> int:
    """Number of data shards the token axis is split over (1 off-mesh)."""
    mesh = current_mesh()
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = sizes.get("pod", 1) * sizes.get("data", 1)
    return dp if dp > 1 and t % dp == 0 else 1


def _local_moe(router_w, w_gate, w_up, w_down, xt, *, n_experts, top_k,
               act, capacity, e_start, e_count):
    """Per-device MoE over a slice of experts (shard_map body helper).

    xt: (t_local, d) — this data shard's tokens (replicated across the
    model axis). w_*: (e_count, …) — this model shard's experts. Returns
    this shard's *partial* output (only its experts' contributions) and
    the local router stats; caller psums over "model".
    """
    act_fn = activation(act)
    t, d = xt.shape
    logits = xt.astype(jnp.float32) @ router_w                 # (t, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    flat_e = top_e.reshape(-1)                                 # (tK,)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, -1) - 1
    mine = (flat_e >= e_start) & (flat_e < e_start + e_count)
    keep = (pos < capacity) & mine
    local_e = jnp.clip(flat_e - e_start, 0, e_count - 1)
    slot = local_e * capacity + jnp.minimum(pos, capacity - 1)

    tok_idx = jnp.repeat(jnp.arange(t), top_k)
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0.0)
    buf = jnp.zeros((e_count * capacity, d), xt.dtype).at[slot].add(
        jnp.where(keep[:, None], contrib, 0.0), mode="drop")
    buf = buf.reshape(e_count, capacity, d)

    h = act_fn(jnp.einsum("ecd,edf->ecf", buf, w_gate)) * jnp.einsum(
        "ecd,edf->ecf", buf, w_up)
    out = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(
        e_count * capacity, d)

    gathered = out[slot]
    w = jnp.where(keep, top_p.reshape(-1), 0.0).astype(xt.dtype)
    y = jnp.zeros((t, d), xt.dtype).at[tok_idx].add(gathered * w[:, None])

    frac = jnp.mean(jax.nn.one_hot(top_e[:, 0], n_experts,
                                   dtype=jnp.float32), axis=0)
    aux = n_experts * jnp.sum(frac * jnp.mean(probs, axis=0))
    return y, aux


def _apply_moe_shardmap(params, x, *, n_experts, top_k, act,
                        capacity_factor, mesh):
    """Expert-parallel MoE via shard_map (EXPERIMENTS.md §Perf climb 1).

    Activations are sharded on batch over ("pod","data") and replicated
    over "model"; experts are sharded over "model". Every model shard
    dispatches the SAME local tokens to ITS expert slice — entirely
    device-local scatter/gather (GSPMD never sees it) — and the partial
    outputs combine with one psum over "model". Collective cost per layer:
    one (t_local, d) all-reduce; the 3.4 TB/step of GSPMD scatter-add
    replication in the global-scatter formulation disappears.
    """
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if a in sizes)
    tp = sizes.get("model", 1)
    b, s, d = x.shape
    dp = 1
    for a in dp_axes:
        dp *= sizes[a]
    tl = (b // dp) * s
    capacity = int(max(1, (tl * top_k * capacity_factor) // n_experts))
    e_count = n_experts // tp

    def body(router_w, w_gate, w_up, w_down, xs):
        midx = jax.lax.axis_index("model")
        xt = xs.reshape(-1, d)
        y, aux = _local_moe(router_w, w_gate, w_up, w_down, xt,
                            n_experts=n_experts, top_k=top_k, act=act,
                            capacity=capacity, e_start=midx * e_count,
                            e_count=e_count)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, dp_axes + ("model",))
        return y.reshape(xs.shape), aux

    in_specs = (P(), P("model", None, None), P("model", None, None),
                P("model", None, None), P(dp_axes, None, None))
    out_specs = (P(dp_axes, None, None), P())
    fn = shard_map(body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_vma=False)
    return fn(params["router"]["w"], params["w_gate"], params["w_up"],
              params["w_down"], x)


def apply_moe(params, x, *, n_experts, top_k, act="silu",
              capacity_factor=1.25, shared_expert=False):
    """x: (B, S, D) -> (y, aux_loss)."""
    b, s, d = x.shape
    t = b * s

    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        dp = sizes.get("pod", 1) * sizes.get("data", 1)
        tp = sizes["model"]
        if n_experts % tp == 0 and b % max(dp, 1) == 0:
            y, aux = _apply_moe_shardmap(
                params, x, n_experts=n_experts, top_k=top_k, act=act,
                capacity_factor=capacity_factor, mesh=mesh)
            if shared_expert:
                from repro.models.blocks import apply_mlp
                y = y + apply_mlp(params["shared"], x, act=act)
            return y, aux
    xt = x.reshape(t, d)
    act_fn = activation(act)

    logits = dense(params["router"], xt.astype(jnp.float32))  # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)                # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)    # renormalize

    ds = _data_shards(t)
    tl = t // ds                                              # tokens/shard
    cap = int(max(1, (tl * top_k * capacity_factor) // n_experts))

    # Shard-local position in expert: cumsum per shard-row.
    flat_e = top_e.reshape(ds, tl * top_k)                    # (DS, tlK)
    onehot = jax.nn.one_hot(flat_e, n_experts, dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=1) * onehot, -1) - 1  # (DS, tlK)
    keep = (pos < cap).reshape(-1)
    shard_ix = jnp.repeat(jnp.arange(ds), tl * top_k)
    slot = ((flat_e.reshape(-1) * ds + shard_ix) * cap
            + jnp.minimum(pos.reshape(-1), cap - 1))          # (T*K,)

    tok_idx = jnp.repeat(jnp.arange(t), top_k)
    contrib = jnp.where(keep[:, None], xt[tok_idx], 0.0)
    contrib = maybe_shard(contrib, ("pod", "data"), None)
    buf = jnp.zeros((n_experts * ds * cap, d), x.dtype).at[slot].add(
        contrib.astype(x.dtype), mode="drop")
    buf = buf.reshape(n_experts, ds * cap, d)
    buf = maybe_shard(buf, "model", ("pod", "data"), None)    # EP × DP

    h = act_fn(jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", buf, params["w_up"])
    out = jnp.einsum("ecf,efd->ecd", h, params["w_down"])
    out = maybe_shard(out, "model", ("pod", "data"), None)
    out = out.reshape(n_experts * ds * cap, d)

    # Gather back with combine weights.
    gathered = out[slot]                                       # (T*K, D)
    gathered = maybe_shard(gathered, ("pod", "data"), None)
    w = jnp.where(keep, top_p.reshape(-1), 0.0).astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[tok_idx].add(gathered * w[:, None])
    y = maybe_shard(y, ("pod", "data"), None)

    if shared_expert:
        from repro.models.blocks import apply_mlp
        # keep (B, S, D) rank for the mlp's activation sharding constraint
        y = y + apply_mlp(params["shared"], x, act=act).reshape(t, d)

    # Switch load-balance aux loss: E · Σ_e f_e · P_e.
    frac = jnp.mean(jax.nn.one_hot(top_e[:, 0], n_experts, dtype=jnp.float32), axis=0)
    mean_p = jnp.mean(probs, axis=0)
    aux = n_experts * jnp.sum(frac * mean_p)

    return y.reshape(b, s, d), aux
