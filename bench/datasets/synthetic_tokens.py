"""``synthetic_tokens``: token sequences for next-token prediction, made on
the host from the configuration's data seed.

Sizes come from ``cfg["data"]``: ``vocab``, ``seq_len``, ``per_client``
sequences a client, ``n_loss`` and ``n_eval`` held-out sequences,
``similarity`` and ``seed``; the population from ``cfg["n_clients"]`` and
``cfg["n_groups"]``.

Token ids are ranks of frequency (id 0 the most frequent). Every sequence
is a Markov chain: each token is, with probability :data:`FOLLOW`, the
successor of the one before it, and otherwise a fresh draw from a Zipf
law of exponent :data:`ZIPF`. A successor table maps each id to an id of
the same octave of ranks (ranks ``2**k`` to ``2**(k+1) - 1``, 1-based), a
permutation of each octave, so an octave's share of the tokens stays the
Zipf law's and the next token depends on the previous one. There are two
tables for each chain: one shared by all, and one of the chain's energy
group (client ``i`` is in group ``i % groups``, held-out sequence ``j`` in
group ``j % groups``), taken at each step with probability ``1 -
similarity``. So the share ``1 - similarity`` of each chain belongs to
its group: data skew aligned with the energy groups, as
``synthetic_confusable`` has it.

Inputs are a chain's first ``seq_len`` tokens, targets the next token at
each position (the chain shifted by one), both ``int32``. Every client
holds the same number of sequences, so the data weights are equal.
"""

from __future__ import annotations

import numpy as np

#: Exponent of the Zipf law of fresh tokens.
ZIPF = 1.1
#: Probability that a token is the previous token's successor.
FOLLOW = 0.75


def zipf_cdf(vocab: int) -> np.ndarray:
    """(vocab,) cumulative probabilities of ids 0..vocab-1."""
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -ZIPF
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


def successor_tables(rng, vocab: int, n: int) -> np.ndarray:
    """(n, vocab) int32 tables, each a permutation of every octave of ranks."""
    out = np.empty((n, vocab), np.int32)
    for i in range(n):
        lo = 0
        while lo < vocab:
            hi = min(2 * lo + 1, vocab)
            out[i, lo:hi] = lo + rng.permutation(hi - lo)
            lo = hi
    return out


def chains(seed: int, groups_of_rows, *, vocab: int, length: int,
           groups: int, similarity: float) -> np.ndarray:
    """(rows, length) int32 chains; row ``r`` belongs to energy group
    ``groups_of_rows[r]``."""
    rng = np.random.default_rng(seed)
    rows = len(groups_of_rows)
    tables = successor_tables(rng, vocab, groups + 1)  # last: shared
    fresh = np.searchsorted(zipf_cdf(vocab),
                            rng.random((length, rows), np.float32))
    fresh = np.minimum(fresh, vocab - 1).astype(np.int32)
    follow = rng.random((length, rows), np.float32) < FOLLOW
    own = rng.random((length, rows), np.float32) >= similarity
    # the table a step uses, as an offset into the flattened tables
    base = np.where(own, np.asarray(groups_of_rows)[None, :],
                    groups).astype(np.int64) * vocab
    flat = tables.reshape(-1)
    out = np.empty((length, rows), np.int32)
    out[0] = tok = fresh[0]
    for t in range(1, length):
        tok = np.where(follow[t], flat[base[t] + tok], fresh[t])
        out[t] = tok
    return np.ascontiguousarray(out.T)


def make(cfg: dict, seed: int) -> dict:
    """The configuration's data: per-client inputs and next tokens
    ``(N, D, S)``, the held-out loss set and the eval set ``(n, S)``."""
    d = cfg["data"]
    n, groups, per = cfg["n_clients"], cfg["n_groups"], d["per_client"]
    held = d["n_loss"] + d["n_eval"]
    group = np.concatenate([np.repeat(np.arange(n) % groups, per),
                            np.arange(held) % groups])
    seqs = chains(seed, group, vocab=d["vocab"], length=d["seq_len"] + 1,
                  groups=groups, similarity=d["similarity"])
    x, y = seqs[:, :-1], seqs[:, 1:]
    train = n * per
    shape = (n, per, d["seq_len"])
    loss_end = train + d["n_loss"]
    return {"shards_x": x[:train].reshape(shape),
            "shards_y": y[:train].reshape(shape),
            "loss_x": x[train:loss_end], "loss_y": y[train:loss_end],
            "eval_x": x[loss_end:], "eval_y": y[loss_end:]}
