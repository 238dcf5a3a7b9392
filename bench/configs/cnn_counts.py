"""Operations the algorithm needs for one image of the CNN of
``cnn_reference.py``, from the configuration's sizes: its convolutions
and dense layers (pooling and normalisation are not counted).

``forward_flops(cfg)`` and ``train_flops(cfg)`` are what the benchmark's
``step_mfu`` reads of any configuration's ``counts`` module."""

from __future__ import annotations


def _sides(model: dict) -> list[int]:
    """Image side at the input of each convolution and after the last
    pool (SAME pools of ``pool_stride``)."""
    sides = [model["image_hw"]]
    for _ in model["conv_channels"]:
        sides.append(-(-sides[-1] // model["pool_stride"]))
    return sides


def _widths(model: dict) -> list[int]:
    """Widths of the dense layers' inputs and of the logits."""
    flat = _sides(model)[-1] ** 2 * model["conv_channels"][-1]
    return [flat, *model["dense_widths"], model["n_classes"]]


def layer_macs(model: dict) -> list[int]:
    """Multiply-adds per image of each layer's forward pass: the SAME
    convolutions, then the dense layers and the logits."""
    k = model["kernel_size"]
    c_in = [model["in_channels"], *model["conv_channels"]]
    convs = [side * side * c_out * k * k * c
             for side, c, c_out in zip(_sides(model), c_in,
                                       model["conv_channels"])]
    widths = _widths(model)
    return convs + [a * b for a, b in zip(widths, widths[1:])]


def forward_flops(cfg: dict) -> int:
    """FLOPs of one image's forward pass (2 per multiply-add)."""
    return 2 * sum(layer_macs(cfg["model"]))


def train_flops(cfg: dict) -> int:
    """FLOPs of one image's forward and backward pass: the forward, the
    weight gradient of every layer, and the input gradient of every
    layer but the first (whose input is the image)."""
    macs = layer_macs(cfg["model"])
    return 2 * (3 * sum(macs) - macs[0])


def params(model: dict) -> int:
    k = model["kernel_size"]
    c_in = [model["in_channels"], *model["conv_channels"]]
    convs = sum((k * k * c + 1) * c_out
                for c, c_out in zip(c_in, model["conv_channels"]))
    widths = _widths(model)
    return convs + sum((a + 1) * b for a, b in zip(widths, widths[1:]))
