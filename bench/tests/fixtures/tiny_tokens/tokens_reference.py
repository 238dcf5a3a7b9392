"""Plain reference of a tiny next-token model: an embedding, one dense
layer with ReLU, and the logits over the vocabulary, applied at every
position of a sequence (a bigram model). Straightforward ``jax.numpy``:
every contraction takes an explicit precision, and the parameters' dtype
is the type every array is held in. It imports nothing of the program."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def init_params(key, model: dict):
    """Seeded float32 weights: normal with the configuration's
    ``init_std``, zero biases."""
    v, e, h = model["vocab_size"], model["embed_dim"], model["hidden_dim"]
    k_emb, k_in, k_out = jax.random.split(key, 3)
    std = model["init_std"]

    def normal(k, shape):
        return std * jax.random.normal(k, shape, jnp.float32)

    return {"embed": normal(k_emb, (v, e)),
            "dense": {"w": normal(k_in, (e, h)),
                      "b": jnp.zeros((h,), jnp.float32)},
            "logits": {"w": normal(k_out, (h, v)),
                       "b": jnp.zeros((v,), jnp.float32)}}


def forward(params, tokens, model: dict, precision=HIGHEST):
    """tokens (B, S) int -> logits (B, S, vocab), in the params' dtype."""
    x = params["embed"][tokens]
    x = jax.nn.relu(jnp.dot(x, params["dense"]["w"], precision=precision)
                    + params["dense"]["b"])
    return (jnp.dot(x, params["logits"]["w"], precision=precision)
            + params["logits"]["b"])


def loss(params, tokens, targets, model: dict, precision=HIGHEST):
    """Mean cross-entropy of the next token over every position."""
    logits = forward(params, tokens, model, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
