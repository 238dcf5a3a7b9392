"""Jit'd public wrapper for flash attention.

Accepts the model's (B, S, H, Dh) layout, transposes to the kernel's
(B, H, S, Dh), selects interpret mode on the CPU backend, and falls back
to the ref for shapes the kernel can't tile (tiny/unaligned smoke
shapes).
"""

from __future__ import annotations

from repro.kernels import interpret_default
from repro.kernels.flash_attention.flash_attention import (
    DEFAULT_BLOCK_K,
    DEFAULT_BLOCK_Q,
    flash_attention_kernel,
)
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q, k, v, *, causal=True, window=0,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Model layout: q (B,S,H,Dh); k,v (B,T,Hkv,Dh) -> (B,S,H,Dh)."""
    qh = q.swapaxes(1, 2)
    kh = k.swapaxes(1, 2)
    vh = v.swapaxes(1, 2)
    s, t = qh.shape[2], kh.shape[2]
    bq, bk = min(block_q, s), min(block_k, t)
    if s % bq or t % bk:
        out = flash_attention_ref(qh, kh, vh, causal=causal, window=window)
    else:
        out = flash_attention_kernel(qh, kh, vh, causal=causal, window=window,
                                     block_q=bq, block_k=bk,
                                     interpret=interpret_default())
    return out.swapaxes(1, 2)
