"""The program's pieces that run the tiny next-token model: the client
batcher over token sequences, per-client gradients vmapped over the
population, plain SGD, and the ``Study`` path that drives them. The
model is written in the program's idiom: float32, contractions at the
backend's default precision, dense layers by ``repro.models.common``."""

from __future__ import annotations


def forward(params, tokens, model: dict):
    """tokens (B, S) int -> logits (B, S, vocab)."""
    import jax
    import jax.numpy as jnp

    from repro.models.common import dense

    x = jnp.take(params["embed"], tokens, axis=0)
    x = jax.nn.relu(dense(params["dense"], x))
    return dense(params["logits"], x)


def loss(params, tokens, targets, model: dict):
    import jax
    import jax.numpy as jnp

    logits = forward(params, tokens, model).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def bind(cfg: dict, data: dict) -> dict:
    """Simulator ingredients over ``data``: ``grads_fn``, ``p``,
    ``optimizer``, ``loss_fn`` and ``use_kernel`` for ``Study.run``, plus
    the ``eval_fn`` of next-token accuracy."""
    import jax
    import jax.numpy as jnp

    from repro.data import ClientBatcher
    from repro.optim import sgd

    model = cfg["model"]
    batcher = ClientBatcher(
        [{"x": x, "y": y} for x, y in zip(data["shards_x"],
                                          data["shards_y"])],
        batch_size=cfg["batch_size"])
    grad_one = jax.grad(lambda p, x, y: loss(p, x, y, model))

    def grads_fn(params, key, t):
        batch = batcher.sample(key)
        return jax.vmap(lambda x, y: grad_one(params, x, y))(batch["x"],
                                                            batch["y"])

    def accuracy(params, x, y):
        logits = forward(params, x, model)
        return jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))

    loss_x, loss_y = jnp.asarray(data["loss_x"]), jnp.asarray(data["loss_y"])
    eval_x, eval_y = jnp.asarray(data["eval_x"]), jnp.asarray(data["eval_y"])
    return {
        "sim": {"grads_fn": grads_fn, "p": batcher.p,
                "optimizer": sgd(cfg["lr"]),
                "loss_fn": lambda params: loss(params, loss_x, loss_y, model),
                "use_kernel": cfg["use_kernel"]},
        "eval_fn": lambda params: accuracy(params, eval_x, eval_y),
    }
