"""FLOPs of one sequence of the tiny next-token model, from the
configuration's sizes: the dense layer and the logits at every position
(the embedding's lookup is not counted)."""

from __future__ import annotations


def forward_flops(cfg: dict) -> int:
    m = cfg["model"]
    macs = m["embed_dim"] * m["hidden_dim"] + m["hidden_dim"] * m["vocab_size"]
    return 2 * cfg["data"]["seq_len"] * macs


def train_flops(cfg: dict) -> int:
    """The forward, and the weight and input gradients of both layers (the
    dense layer's input gradient is the embedding's)."""
    return 3 * forward_flops(cfg)
