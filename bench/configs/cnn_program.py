"""The program's pieces that run the CNN configurations: the client
batcher, per-client gradients vmapped over the population as the Fig-1
example builds them, plain SGD, and the ``Study`` path that drives them
(the simulator step, the fused server update, the engine).

The client model is the configuration's (the program's own
``repro.models.cnn`` has other widths): its forward pass is written
here in the program's idiom, float32 with contractions at the backend's
default precision, and its dense layers are the program's
``repro.models.common.dense``."""

from __future__ import annotations


def forward(params, images, model: dict):
    """images (B, H, W, C) -> logits (B, classes)."""
    import jax
    import jax.numpy as jnp

    from repro.models.common import dense

    lrn = model["lrn"]
    w, s = model["pool_window"], model["pool_stride"]

    def conv(p, x):
        y = jax.lax.conv_general_dilated(
            x, p["w"], window_strides=(1, 1), padding="SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        return jax.nn.relu(y + p["b"])

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, w, w, 1),
                                     (1, s, s, 1), "SAME")

    def norm(x):
        r = lrn["depth_radius"]
        total = jax.lax.reduce_window(x * x, 0.0, jax.lax.add,
                                      (1, 1, 1, 2 * r + 1), (1, 1, 1, 1),
                                      [(0, 0)] * 3 + [(r, r)])
        return x * (lrn["bias"] + lrn["alpha"] * total) ** -lrn["beta"]

    x = norm(pool(conv(params["conv1"], images)))
    x = pool(norm(conv(params["conv2"], x)))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(params["local3"], x))
    x = jax.nn.relu(dense(params["local4"], x))
    return dense(params["softmax_linear"], x)


def loss(params, images, labels, model: dict):
    import jax
    import jax.numpy as jnp

    logits = forward(params, images, model).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def bind(cfg: dict, data: dict) -> dict:
    """Simulator ingredients over ``data`` (see ``harness.data.make``):
    ``grads_fn``, ``p``, ``optimizer``, ``loss_fn`` and ``use_kernel`` for
    ``Study.run``, plus the ``eval_fn`` of the accuracy evaluation."""
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
