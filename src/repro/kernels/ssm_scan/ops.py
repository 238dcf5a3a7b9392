"""Jit'd public wrapper for the GLA/SSD scan kernel.

Model layout (B, S, H, D*) is folded to the kernel's (B·H, S, D*);
sequence is padded to the chunk size with a=1, k=v=0 (identity steps).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import interpret_default
from repro.kernels.ssm_scan.ssm_scan import gla_scan_kernel


def gla_scan(a, k, v, q, chunk: int = 64):
    """a: (B,S,H); k,q: (B,S,H,dk); v: (B,S,H,dv) -> y (B,S,H,dv) f32."""
    b, s, h = a.shape
    dk, dv = k.shape[-1], v.shape[-1]
    fold = lambda x: x.swapaxes(1, 2).reshape((b * h, s) + x.shape[3:])
    af, kf, vf, qf = fold(a), fold(k), fold(v), fold(q)
    pad = (-s) % chunk
    if pad:
        af = jnp.pad(af, ((0, 0), (0, pad)), constant_values=1.0)
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0)))
        qf = jnp.pad(qf, ((0, 0), (0, pad), (0, 0)))
    y = gla_scan_kernel(af, kf, vf, qf, chunk=min(chunk, af.shape[1]),
                        interpret=interpret_default())
    y = y[:, :s]
    return y.reshape(b, h, s, dv).swapaxes(1, 2)
