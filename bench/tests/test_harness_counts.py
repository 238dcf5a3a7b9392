"""FLOP and byte counts of the benchmark against hand counts."""

import json

from bench_tiny import BENCH
from metrics import _counts

MODEL = json.loads((BENCH / "configs" / "fig1_cnn.json").read_text())["model"]

# conv1: 24x24 outputs x 64 channels x 5x5x3; conv2: 12x12 (after a
# stride-2 SAME pool) x 64 x 5x5x64; local3: 6x6x64 -> 384; local4:
# 384 -> 192; logits: 192 -> 10.
MACS = [2_764_800, 14_745_600, 884_736, 73_728, 1_920]


def test_cnn_forward_macs_by_hand():
    assert _counts.cnn_layer_macs(MODEL) == MACS
    assert sum(MACS) == 18_470_784
    assert _counts.cnn_forward_flops(MODEL) == 2 * 18_470_784


def test_cnn_train_flops_leave_out_the_image_gradient():
    assert _counts.cnn_train_flops(MODEL) == 2 * (3 * 18_470_784
                                                  - 2_764_800)


def test_cnn_params_match_the_published_model():
    # McMahan et al.'s "about 10^6": 4,864 + 102,464 + 885,120 + 73,920
    # + 1,930
    assert _counts.cnn_params(MODEL) == 1_068_298 == MODEL["n_params"]


def test_update_bytes_read_rows_and_params_write_params():
    assert _counts.update_bytes(1_068_298, 40) == 4 * 1_068_298 * 42
    assert _counts.update_bytes(10, 0) == 80


def test_reference_init_has_the_counted_params():
    import jax

    from harness.main import load_module

    ref = load_module(BENCH / "configs" / "cnn_reference.py", "ref_counts")
    shapes = jax.eval_shape(lambda k: ref.init_params(k, MODEL),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 1_068_298
