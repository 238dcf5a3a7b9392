"""Plain reference of the CIFAR-10 CNN of McMahan et al. (arXiv:1602.05629
§3, "about 10^6 parameters"), the TensorFlow CIFAR-10 tutorial's model,
as arXiv:2102.05639 §V uses it: on 24x24x3 images, a 5x5 SAME
convolution of 64 channels with ReLU, a 3x3 stride-2 SAME max-pool and a
local response normalisation; a second 5x5 convolution of 64 channels
with ReLU, normalisation and pool; dense layers of 384 and 192 with
ReLU; a linear layer over the classes. NHWC images, HWIO kernels.

Straightforward ``jax.numpy``: every contraction takes an explicit
precision, and ``dtype`` is the type every array is held in. The
benchmark runs it in float32 at ``highest`` precision as the reference
and in bfloat16 as the control. It imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

#: Layers in order, with the configuration keys of their widths.
CONVS = ("conv1", "conv2")
DENSES = ("local3", "local4")


def init_params(key, model: dict):
    """Seeded float32 weights as the tutorial initialises them: kernels
    from a normal truncated at two standard deviations with the
    configuration's ``init`` scales, biases at its constants."""
    init = model["init"]
    k, c_in = model["kernel_size"], model["in_channels"]
    convs = model["conv_channels"]
    side = model["image_hw"]
    for _ in convs:
        side = -(-side // model["pool_stride"])
    widths = [side * side * convs[-1], *model["dense_widths"]]
    keys = iter(jax.random.split(key, len(convs) + len(widths)))

    def layer(shape, std, bias):
        w = jax.random.truncated_normal(next(keys), -2.0, 2.0, shape,
                                        jnp.float32) * std
        return {"w": w, "b": jnp.full(shape[-1:], bias, jnp.float32)}

    params = {}
    for name, c_out, std, bias in zip(CONVS, convs, init["conv_std"],
                                      init["conv_bias"]):
        params[name] = layer((k, k, c_in, c_out), std, bias)
        c_in = c_out
    for name, d_in, d_out, std, bias in zip(
            DENSES, widths, widths[1:], init["dense_std"],
            init["dense_bias"]):
        params[name] = layer((d_in, d_out), std, bias)
    params["softmax_linear"] = layer(
        (widths[-1], model["n_classes"]), init["logits_std"],
        init["logits_bias"])
    return params


def forward(params, images, model: dict, precision=HIGHEST):
    """images (B, H, W, C) -> logits (B, classes), in the params' dtype."""
    lrn = model["lrn"]
    w, s = model["pool_window"], model["pool_stride"]

    def conv(p, x):
        y = jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        return jax.nn.relu(y + p["b"])

    def pool(x):
        return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, w, w, 1),
                                     (1, s, s, 1), "SAME")

    def norm(x):
        # x / (bias + alpha * sum of squares over the 2r+1 nearest
        # channels) ** beta
        r, c = lrn["depth_radius"], x.shape[-1]
        sq = jnp.pad(x * x, [(0, 0)] * 3 + [(r, r)])
        total = sum(sq[..., i:i + c] for i in range(2 * r + 1))
        return x / (lrn["bias"] + lrn["alpha"] * total) ** lrn["beta"]

    def dense(p, x):
        return jnp.dot(x, p["w"], precision=precision) + p["b"]

    x = norm(pool(conv(params["conv1"], images)))
    x = pool(norm(conv(params["conv2"], x)))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(dense(params["local3"], x))
    x = jax.nn.relu(dense(params["local4"], x))
    return dense(params["softmax_linear"], x)


def loss(params, images, labels, model: dict, precision=HIGHEST):
    """Mean cross-entropy over the batch, in the params' dtype."""
    logits = forward(params, images, model, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)
