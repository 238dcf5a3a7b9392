"""The comparison that decides ``correct`` fails its control and every
fault a one-chip cell can have.

The control is the reference itself computed in bfloat16 (the precision
below the configuration's float32) in the program's place. The faults
are planted in the program underneath a whole run of the harness, with
its look for a chip skipped: a step that returns its state unchanged,
half of each client's batch left out (the mean taken over the rest), and
an answer altered where it is produced."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from bench_tiny import run_tiny, shrink
from harness import check, data as data_mod, main


def _reference(seed):
    c = shrink(main.load_cell("fig1_cnn.grid"))
    cfg = c["cfg"]
    model = main.config_module(c, "reference")
    init = jax.jit(partial(model.init_params, model=cfg["model"]))
    return cfg, check.Reference(cfg, model, data_mod.make(cfg, seed),
                                lambda: init(jax.random.PRNGKey(seed)))


def test_reference_agrees_with_itself():
    cfg, ref = _reference(11)
    answers = [dict(ref.answer(s, n, 40 + n, 4), scheduler=s, n_clients=n,
                    seed=40 + n)
               for s in ("alg1", "benchmark2") for n in (6, 12)]
    ok, found = check.judge(check.compare(ref, answers), cfg["limits"])
    assert ok, found
    assert all(v["value"] == 0 for v in found.values())


def test_control_in_bfloat16_is_not_correct():
    cfg, ref = _reference(12)
    answers = [dict(ref.answer(s, n, 70 + n, 4, dtype=jnp.bfloat16),
                    scheduler=s, n_clients=n, seed=70 + n)
               for s in ("alg1", "benchmark1", "benchmark2", "oracle")
               for n in (6, 12)]
    ok, found = check.judge(check.compare(ref, answers), cfg["limits"])
    assert not ok, found


def _unchanged_step(monkeypatch):
    from repro.core import aggregation

    monkeypatch.setattr(
        aggregation, "fused_flat_sgd_update",
        lambda g, w, params, opt_state, opt, **kw: (params, opt_state, None))


def _half_batch(monkeypatch):
    from repro.data import ClientBatcher

    sample = ClientBatcher.sample

    def half(self, key):
        return {k: v[:, :self.batch_size // 2]
                for k, v in sample(self, key).items()}

    monkeypatch.setattr(ClientBatcher, "sample", half)


def _altered_answer(monkeypatch):
    from repro.core.trainer import ClientSimulator

    history = ClientSimulator._history

    def altered(outs):
        part = outs["participation"]
        outs = dict(outs, participation=part.at[0, 0].set(1 - part[0, 0]))
        return history(outs)

    monkeypatch.setattr(ClientSimulator, "_history", staticmethod(altered))


@pytest.mark.parametrize("fault", [_unchanged_step, _half_batch,
                                   _altered_answer])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = run_tiny(monkeypatch, "fig1_cnn.grid", seed=2**31 + 17)
    assert result["correct"] is False, result["compared"]


def test_change_gap_by_the_worst_leaf():
    p0 = {"a": np.zeros(4), "b": np.zeros(9), "c": np.ones(2),
          "d": np.zeros(1)}
    ref = {"a": np.full(4, 0.5), "b": np.full(9, 1 / 3),
           "c": np.array([1.0, 1.1]), "d": np.full(1, 1e-4)}
    # the reference's change by leaf: a 1, b 1, c 0.1, d 1e-4 (under a
    # thousandth of the median, so left out)
    assert check.change_gap(ref, ref, p0) == 0
    assert check.change_gap(p0, dict(ref, d=np.zeros(1)), p0) == 1
    double = {k: 2 * v - p0[k] for k, v in ref.items()}
    assert check.change_gap(double, ref, p0) == pytest.approx(1)
    # c moved 0.6 where the reference moved 0.1: 0.5 over the median
    # leaf's 0.55 (between 0.1 and 1)
    assert check.change_gap(dict(ref, c=np.array([1.0, 1.6])), ref,
                            p0) == pytest.approx(0.5 / 0.55)
    assert check.change_gap(dict(ref, d=np.full(1, 5.0)), ref, p0) == 0
    assert check.change_gap(dict(ref, a=np.full(4, np.nan)), ref, p0) \
        == float("inf")


def test_a_reading_that_is_not_finite_is_not_correct():
    limits = dict.fromkeys(check.NUMBERS, 1.0)
    found = dict.fromkeys(check.NUMBERS, 0.0)
    assert check.judge(found, limits)[0]
    ok, out = check.judge(dict(found, loss_gap=float("nan")), limits)
    assert not ok and out["loss_gap"]["value"] == "nan"
