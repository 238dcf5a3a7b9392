"""``synthetic_confusable``: client images made on the host from the
configuration's data seed.

It stands in for CIFAR-10 with the same tensor shapes: class ``c``'s
prototype is ``similarity`` parts the shared prototype of energy group
``c % groups`` and the rest its own, both smooth 4x4 fields upsampled to
the image size; an example is its class prototype plus Gaussian noise. Clients of group ``g`` (client ``i`` is in
group ``i % groups``) hold examples of the classes ``c % groups == g`` in
the share ``label_skew``, the rest drawn from what is left over. Every
client holds the same number of examples, so the data weights are equal.
"""

from __future__ import annotations

import numpy as np


def synthetic_confusable(seed: int, n: int, *, hw: int, channels: int,
                         classes: int, groups: int, similarity: float,
                         noise: float):
    """(n, hw, hw, channels) float32 images and (n,) int32 labels."""
    rng = np.random.default_rng(seed)
    lo = 4
    shared = rng.standard_normal((groups, lo, lo, channels), np.float32)
    unique = rng.standard_normal((classes, lo, lo, channels), np.float32)
    protos = (similarity * shared[np.arange(classes) % groups]
              + (1 - similarity) * unique)
    rep = -(-hw // lo)
    protos = np.repeat(np.repeat(protos, rep, 1), rep, 2)[:, :hw, :hw]
    labels = rng.integers(0, classes, n).astype(np.int32)
    images = protos[labels] + np.float32(noise) * rng.standard_normal(
        (n, hw, hw, channels), np.float32)
    return images.astype(np.float32), labels


def group_skew_partition(seed: int, labels, n_clients: int, groups: int,
                         skew: float) -> np.ndarray:
    """(n_clients, len(labels) // n_clients) example indices per client."""
    rng = np.random.default_rng(seed + 1)
    per = len(labels) // n_clients
    pools = [list(rng.permutation(np.flatnonzero(labels % groups == g)))
             for g in range(groups)]
    taken = np.zeros(len(labels), bool)
    rows = np.zeros((n_clients, per), np.int64)
    fill = []
    for i in range(n_clients):
        pool = pools[i % groups]
        own = [pool.pop() for _ in range(min(int(skew * per), len(pool)))]
        rows[i, :len(own)] = own
        taken[own] = True
        fill.append(len(own))
    rest = list(rng.permutation(np.flatnonzero(~taken)))
    for i in range(n_clients):
        for j in range(fill[i], per):
            rows[i, j] = rest.pop()
    return rows


def make(cfg: dict, seed: int) -> dict:
    """The configuration's data: per-client shards ``(N, D, ...)``, their
    labels ``(N, D)``, the held-out loss set and the eval set."""
    d, m = cfg["data"], cfg["model"]
    images, labels = synthetic_confusable(
        seed, d["n_train"] + d["n_eval"], hw=m["image_hw"],
        channels=m["in_channels"], classes=m["n_classes"],
        groups=cfg["n_groups"], similarity=d["similarity"],
        noise=d["noise"])
    train_y = labels[:d["n_train"]]
    rows = group_skew_partition(seed, train_y, cfg["n_clients"],
                                cfg["n_groups"], d["label_skew"])
    eval_x, eval_y = images[d["n_train"]:], labels[d["n_train"]:]
    return {"shards_x": images[rows], "shards_y": train_y[rows],
            "loss_x": eval_x[:d["n_loss"]], "loss_y": eval_y[:d["n_loss"]],
            "eval_x": eval_x, "eval_y": eval_y}
