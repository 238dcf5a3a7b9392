"""The data sets that configurations name (``bench/datasets``): the CNN's
images as they were made before the data sets moved there, and the
token sequences for next-token client models."""

import hashlib

import numpy as np
import pytest
from bench_tiny import BENCH, shrink
from harness import data as data_mod, main

tokens = main.load_module(BENCH / "datasets" / "synthetic_tokens.py",
                          "synthetic_tokens")

KEYS = ("shards_x", "shards_y", "loss_x", "loss_y", "eval_x", "eval_y")

# sha256 over each array's key, dtype, shape and bytes, as made for
# fig1_cnn (data seed 0) by the harness before the data sets moved
FIG1_CNN = {
    "shrunk": "f3c91ac7f6f81caab6af2ebe81e2256437bb9e17c9e30a0b2dc8518129970e14",
    "full": "6498e47df573d60eca313fb6a5e8777cea33f699c7659abf0ab155bc73530103",
}


def digest(data: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(data):
        a = data[k]
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("size", sorted(FIG1_CNN))
def test_synthetic_confusable_is_bitwise_what_it_was(size):
    c = main.load_cell("fig1_cnn.grid")
    if size == "shrunk":
        c = shrink(c)
    cfg = c["cfg"]
    assert cfg["dataset"] == "synthetic_confusable"
    assert digest(data_mod.make(cfg, cfg["data"]["seed"])) == FIG1_CNN[size]


def test_unknown_dataset_is_an_error():
    with pytest.raises(ValueError, match="synthetic_tokens"):
        data_mod.make({"dataset": "cifar10"}, 0)


def token_cfg(**data) -> dict:
    return {"n_clients": 8, "n_groups": 4, "dataset": "synthetic_tokens",
            "data": {"vocab": 256, "seq_len": 64, "per_client": 8,
                     "n_loss": 6, "n_eval": 10, "similarity": 0.5,
                     "seed": 0, **data}}


def test_synthetic_tokens_is_deterministic_in_its_seed():
    cfg = token_cfg()
    a, b = data_mod.make(cfg, 3), data_mod.make(cfg, 3)
    assert digest(a) == digest(b)
    assert digest(a) != digest(data_mod.make(cfg, 4))


def test_synthetic_tokens_shapes_dtypes_and_next_tokens():
    d = data_mod.make(token_cfg(), 1)
    assert set(d) == set(KEYS)
    shapes = {"shards_x": (8, 8, 64), "shards_y": (8, 8, 64),
              "loss_x": (6, 64), "loss_y": (6, 64),
              "eval_x": (10, 64), "eval_y": (10, 64)}
    for k, shape in shapes.items():
        assert d[k].shape == shape and d[k].dtype == np.int32, k
        assert 0 <= d[k].min() and d[k].max() < 256, k
    for x, y in (("shards_x", "shards_y"), ("loss_x", "loss_y"),
                 ("eval_x", "eval_y")):
        # the target at each position is the next input
        np.testing.assert_array_equal(d[y][..., :-1], d[x][..., 1:])


def _bigram_l1(similarity: float) -> tuple[float, float]:
    """Mean L1 distance between clients' bigram frequencies (previous
    token 1 to 63) within an energy group and across groups."""
    d = data_mod.make(token_cfg(seq_len=256, per_client=32,
                                similarity=similarity), 0)
    freq = []
    for x, y in zip(d["shards_x"], d["shards_y"]):
        x, y = x.ravel(), y.ravel()
        keep = (x >= 1) & (x < 64)
        c = np.bincount(x[keep] * 256 + y[keep], minlength=64 * 256)
        freq.append(c / c.sum())
    pairs = [(i, j) for i in range(8) for j in range(i + 1, 8)]
    same = [np.abs(freq[i] - freq[j]).sum() for i, j in pairs
            if i % 4 == j % 4]
    cross = [np.abs(freq[i] - freq[j]).sum() for i, j in pairs
             if i % 4 != j % 4]
    return float(np.mean(same)), float(np.mean(cross))


def test_synthetic_tokens_skew_follows_the_energy_groups():
    same, cross = _bigram_l1(0.5)
    assert cross > 1.5 * same
    # with no share of its own, a group's chains are like any other's
    same, cross = _bigram_l1(1.0)
    assert cross < 1.15 * same


def test_synthetic_tokens_follow_zipf():
    cfg = {"n_clients": 40, "n_groups": 4,
           "data": {"vocab": 32_768, "seq_len": 512, "per_client": 64,
                    "n_loss": 8, "n_eval": 8, "similarity": 0.9,
                    "seed": 0}}
    x = tokens.make(cfg, 7)["shards_x"]
    freq = np.sort(np.bincount(x.ravel(), minlength=32_768))[::-1]
    rank = np.arange(1, 1001)
    slope = np.polyfit(np.log(rank), np.log(freq[:1000]), 1)[0]
    assert abs(slope + tokens.ZIPF) <= 0.2, slope
