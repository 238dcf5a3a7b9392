"""FLOP counts of the benchmark against hand counts, and ``step_mfu``
read through the ``counts`` module a configuration names."""

import json

import pytest
from bench_tiny import BENCH
from harness.main import Run, config_module, load_cell, load_module

CFG = json.loads((BENCH / "configs" / "fig1_cnn.json").read_text())
MODEL = CFG["model"]
counts = load_module(BENCH / "configs" / "cnn_counts.py", "cnn_counts")

# conv1: 24x24 outputs x 64 channels x 5x5x3; conv2: 12x12 (after a
# stride-2 SAME pool) x 64 x 5x5x64; local3: 6x6x64 -> 384; local4:
# 384 -> 192; logits: 192 -> 10.
MACS = [2_764_800, 14_745_600, 884_736, 73_728, 1_920]


def test_cnn_forward_macs_by_hand():
    assert counts.layer_macs(MODEL) == MACS
    assert sum(MACS) == 18_470_784
    assert counts.forward_flops(CFG) == 2 * 18_470_784


def test_cnn_train_flops_leave_out_the_image_gradient():
    assert counts.train_flops(CFG) == 2 * (3 * 18_470_784 - 2_764_800)


def test_cnn_params_match_the_published_model():
    # McMahan et al.'s "about 10^6": 4,864 + 102,464 + 885,120 + 73,920
    # + 1,930
    assert counts.params(MODEL) == 1_068_298 == MODEL["n_params"]


def test_reference_init_has_the_counted_params():
    import jax

    ref = load_module(BENCH / "configs" / "cnn_reference.py", "ref_counts")
    shapes = jax.eval_shape(lambda k: ref.init_params(k, MODEL),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == 1_068_298


@pytest.mark.parametrize("workload", ["fig1_cnn.grid", "fig1_cnn.oracle"])
def test_step_mfu_reads_the_configurations_counts(workload):
    """The counts module the configuration names gives the hand-counted
    CNN FLOPs: 16 images a participating client, 512 held-out images a
    loss, 800 an accuracy."""
    c = load_cell(workload)
    assert c["cfg"] == CFG
    facts = {"client_rounds": 5_000, "loss_evals": 400, "acc_evals": 16}
    run = Run(facts=facts, record={}, cfg=c["cfg"],
              counts=config_module(c, "counts"), window_s=40.0, chips=1,
              peaks={"bf16_flops_per_s": 197e12})
    flops = (5_000 * 16 * 2 * (3 * 18_470_784 - 2_764_800)
             + (400 * 512 + 16 * 800) * 2 * 18_470_784)
    reader = load_module(BENCH / "metrics" / "step_mfu.py", "step_mfu")
    assert reader.read(run) == pytest.approx(100 * flops / (40.0 * 197e12),
                                             rel=1e-12)
    assert reader.read(Run(**{**run.__dict__, "record": None})) is None
