"""Whether the timed path's answers are correct: a comparison with the
configuration's plain reference (:mod:`harness.fedref` and the model
reference named by the configuration).

An answer is one (scheduler, population, seed) run the window produced,
as the user got it on the host: its ``participation`` (rounds, n),
``weight_sum`` (rounds,), held-out ``loss`` after each round (rounds,)
and final ``params``. The reference recomputes the masks and weight sums
of every round and follows the model through the whole run. Per answer:

* ``mask_mismatch``: (round, client) entries whose participation differs;
* ``wsum_gap``: the largest gap of a round's weight sum;
* ``loss_gap``: the largest gap of the held-out loss after one of the
  first ``compare_rounds`` rounds, as a share of the reference's loss;
* ``change_gap``: the parameters' change over the run by the worst leaf:
  the gap between the norm of the answer's change of a leaf and the
  reference's, over the larger of the reference's norm of that leaf and
  of the median leaf. Leaves the reference moves by less than a
  thousandth of the median leaf are left out (moved by round-off alone).

The numbers compared are the largest over the sampled answers.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from harness import fedref

NUMBERS = ("mask_mismatch", "wsum_gap", "loss_gap", "change_gap")


def sample(answers, k: int, rng) -> list:
    """``k`` answers drawn from the seed, spread evenly over schedulers."""
    by: dict[str, list] = {}
    for a in answers:
        by.setdefault(a["scheduler"], []).append(a)
    per = -(-k // max(len(by), 1))
    out = []
    for name in sorted(by):
        pick = rng.choice(len(by[name]), min(per, len(by[name])),
                          replace=False)
        out.extend(by[name][i] for i in sorted(pick))
    return out


class Model:
    """The configuration's plain model with its sizes bound: what
    :mod:`harness.fedref` calls, as a static argument of its jitted
    functions (equal for the same module and sizes)."""

    def __init__(self, module, sizes: dict):
        self.module, self.sizes = module, sizes

    def _key(self):
        return id(self.module), json.dumps(self.sizes, sort_keys=True)

    def __hash__(self):
        return hash(self._key())

    def __eq__(self, other):
        return isinstance(other, Model) and self._key() == other._key()

    def loss(self, params, x, y, precision):
        return self.module.loss(params, x, y, self.sizes, precision)


class Reference:
    """The reference over one configuration's data and initial weights
    (host copies, placed on the device when the reference first runs)."""

    def __init__(self, cfg: dict, model, data: dict, params0_fn):
        self.cfg, self.model = cfg, Model(model, cfg["model"])
        self.data = data
        self.params0_fn = params0_fn
        self._dev = None

    def _device(self):
        if self._dev is None:
            d = self.data
            self._dev = {k: jnp.asarray(d[k]) for k in
                         ("shards_x", "shards_y", "loss_x", "loss_y")}
            self._dev["params0"] = self.params0_fn()
            self.params0 = jax.tree_util.tree_map(np.asarray,
                                                  self._dev["params0"])
        return self._dev

    def answer(self, scheduler: str, n: int, seed: int, rounds: int,
               dtype=jnp.float32, keep: int | None = None) -> dict:
        """The reference's answer: masks and weight sums of every round,
        held-out losses after the first ``compare_rounds`` rounds, final
        parameters.
        ``dtype`` and ``keep`` are for the control readings
        (``fedref.follow``)."""
        cfg, dev = self.cfg, self._device()
        n_cap = cfg["n_clients"]
        taus = [cfg["taus"][i % len(cfg["taus"])] for i in range(n)]
        k_sched, k_grad = fedref.round_keys(jax.random.PRNGKey(seed), rounds)
        u = np.asarray(fedref.client_uniforms(k_sched, n_cap))
        masks, scales = fedref.schedule(scheduler, taus, n_cap, rounds, u)
        p = fedref.data_weights(np.full(n_cap, dev["shards_y"].shape[1]),
                                n, n_cap)
        weights = (p * masks * scales).astype(np.float32)
        losses, params = fedref.follow(
            self.model, dev["params0"], dev["shards_x"], dev["shards_y"],
            dev["loss_x"], dev["loss_y"], weights, k_grad,
            batch=cfg["batch_size"], lr=cfg["lr"], dtype=dtype,
            block=cfg.get("reference_block"), keep=keep,
            loss_rounds=min(cfg["compare_rounds"], rounds))
        wsum = np.asarray(jnp.sum(jnp.asarray(weights, dtype), axis=1),
                          np.float64)
        return {"participation": masks[:, :n], "weight_sum": wsum,
                "loss": losses, "params": params}


def change_gap(params, want, params0) -> float:
    """``change_gap`` of final ``params`` against the reference's final
    ``want``, both from ``params0`` (module docstring)."""
    def norms(final):
        return [float(np.linalg.norm(np.asarray(f, np.float64)
                                     - np.asarray(p, np.float64)))
                for f, p in zip(jax.tree_util.tree_leaves(final),
                                jax.tree_util.tree_leaves(params0))]

    got, ref = norms(params), norms(want)
    if not np.all(np.isfinite(got)):
        return float("inf")
    median = float(np.median(ref))
    return max((abs(g - r) / max(r, median) for g, r in zip(got, ref)
                if r >= 1e-3 * median), default=0.0)


def numbers(answer: dict, ref: dict, params0) -> dict:
    """The numbers of one answer against the reference's."""
    want = ref["loss"]
    got = np.asarray(answer["loss"][:len(want)], np.float64)
    return {
        "mask_mismatch": float(np.sum(
            np.asarray(answer["participation"]) != ref["participation"])),
        "wsum_gap": float(np.max(np.abs(
            np.asarray(answer["weight_sum"], np.float64)
            - ref["weight_sum"]))),
        "loss_gap": float(np.max(np.abs(got - want) / np.abs(want))),
        "change_gap": change_gap(answer["params"], ref["params"], params0),
    }


def compare(reference: Reference, answers, *, dtype=jnp.float32) -> dict:
    """Largest of each number over ``answers``."""
    worst = dict.fromkeys(NUMBERS, 0.0)
    for a in answers:
        ref = reference.answer(a["scheduler"], a["n_clients"], a["seed"],
                               len(a["weight_sum"]), dtype)
        for name, v in numbers(a, ref, reference.params0).items():
            worst[name] = max(worst[name], v) if np.isfinite(v) else np.inf
    return worst


def judge(found: dict, limits: dict) -> tuple[bool, dict]:
    """(all within their limits, {name: {"value", "limit"}})."""
    ok = all(np.isfinite(found[name]) and found[name] <= limits[name]
             for name in NUMBERS)
    # JSON has no infinity: a non-finite reading is written as text
    out = {name: {"value": (found[name] if np.isfinite(found[name])
                            else repr(found[name])),
                  "limit": limits[name]}
           for name in NUMBERS}
    return ok, out
