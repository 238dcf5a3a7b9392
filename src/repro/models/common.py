"""Shared model utilities: sharding hints, norms, linears, activations.

Parameters are plain nested dicts of jnp arrays; every layer is an
``init_*(key, ...) -> params`` plus a pure ``apply`` function. Sharding is
annotated *inside* the model via :func:`maybe_shard`, which is a no-op
outside a mesh context (CPU smoke tests) and a
``with_sharding_constraint`` inside one (dry-run / production) — the
MaxText pattern.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from jax._src.mesh import thread_resources as _thread_resources


def current_mesh():
    mesh = _thread_resources.env.physical_mesh
    return None if mesh.empty else mesh


import os

# Perf-iteration toggle (EXPERIMENTS.md §Perf): divisibility-aware
# activation sharding. When on (default), a constraint axis that does not
# evenly divide the tensor dim is dropped instead of handed to GSPMD —
# non-divisible constraints (e.g. 8 kv heads on a 16-way model axis)
# trigger "involuntary full rematerialization" resharding copies.
_DIVCHECK = os.environ.get("REPRO_DIVCHECK", "1") != "0"


def maybe_shard(x, *spec):
    """Constrain ``x`` to PartitionSpec(*spec) if a mesh is active.

    Axis names absent from the active mesh are dropped (so the same model
    code runs on (data, model), (pod, data, model) or no mesh at all), as
    are axes that don't divide the corresponding dim (see _DIVCHECK).
    Entries may be None, a name, or a tuple of names.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def _filter(entry, dim):
        if entry is None:
            return None
        axes = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
        kept = tuple(a for a in axes if a in sizes)
        if not kept:
            return None
        if _DIVCHECK:
            total = 1
            for a in kept:
                total *= sizes[a]
            if dim % total != 0:
                return None
        return kept if len(kept) > 1 else kept[0]

    filtered = tuple(_filter(e, d) for e, d in zip(spec, x.shape))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*filtered)))


# ---------------------------------------------------------------- initializers

def normal_init(key, shape, dtype, stddev):
    return (stddev * jax.random.normal(key, shape)).astype(dtype)


def lecun_init(key, shape, dtype, fan_in=None):
    fan_in = fan_in or shape[0]
    return normal_init(key, shape, dtype, fan_in ** -0.5)


# ---------------------------------------------------------------- primitives

def dense_init(key, d_in, d_out, dtype, use_bias=False, stddev=None):
    p = {"w": lecun_init(key, (d_in, d_out), dtype) if stddev is None
         else normal_init(key, (d_in, d_out), dtype, stddev)}
    if use_bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(params, x):
    y = x @ params["w"]
    if "b" in params:
        y = y + params["b"]
    return y


def norm_init(d, dtype, kind="rmsnorm"):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(params, x, kind="rmsnorm", eps=1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:  # layernorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32)
    if "bias" in params:
        y = y + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def activation(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------- rotary

def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x, positions, theta=1e4):
    """Rotary embedding. x: (..., S, H, Dh); positions: broadcastable (..., S)."""
    dh = x.shape[-1]
    freqs = rope_freqs(dh, theta)  # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    ang = ang[..., None, :]  # head axis
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, theta=1e4, sections=(16, 24, 24)):
    """Qwen2-VL M-RoPE [arXiv:2409.12191]: the Dh/2 frequency slots are
    partitioned into (temporal, height, width) sections, each rotated by
    its own position stream. positions3: (3, ..., S)."""
    dh = x.shape[-1]
    half = dh // 2
    sections = tuple(sections)
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(dh, theta)  # (half,)
    # Build per-slot position: (..., S, half)
    pos_parts = []
    start = 0
    for k, sec in enumerate(sections):
        p = positions3[k][..., None].astype(jnp.float32)
        pos_parts.append(jnp.broadcast_to(p, p.shape[:-1] + (sec,)))
        start += sec
    pos = jnp.concatenate(pos_parts, axis=-1)  # (..., S, half)
    ang = (pos * freqs)[..., None, :]  # head axis
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- misc

def stack_init(key, n, init_fn):
    """vmap an init over a leading layer axis -> stacked params for scan."""
    keys = jax.random.split(key, n)
    return jax.vmap(init_fn)(keys)


def count_params(tree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(tree))
