"""A token-sequence configuration made of new files only — the fixture in
``fixtures/tiny_tokens``: its configuration file, plain reference,
program and counts, over the ``synthetic_tokens`` data set — runs
through the harness on the CPU and reads ``correct``; the bfloat16
control in the program's place, and faults planted under a whole run,
read not ``correct``.

The benchmark's specification is ``BENCHMARK.json`` with the entries a
configuration of its own would append: the configuration, a cell under
an existing traffic mix, and the cell's name in every metric's list of
``workloads`` that names ``fig1_cnn.grid``."""

import json
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from bench_tiny import BENCH, ROOT, run_tiny
from harness import check, data as data_mod, main

FIXTURE = "bench/tests/fixtures/tiny_tokens/tiny_tokens.json"
CELL = "tiny_tokens.grid"


def spec() -> dict:
    out = json.loads((ROOT / "BENCHMARK.json").read_text())
    out["configs"].append({
        "name": "tiny_tokens", "source": "a test fixture", "file": FIXTURE,
        "reduced": [], "why": "a next-token client model"})
    out["workloads"].append({
        "name": CELL, "config": "tiny_tokens", "traffic": "grid",
        "chips": 1, "why": "the Fig-1 grid over token sequences"})
    for m in out["end_to_end"] + out["per_layer"]:
        if "fig1_cnn.grid" in m.get("workloads", []):
            m["workloads"].append(CELL)
    return out


def test_the_fixture_names_no_file_of_the_harness():
    c = main.load_cell(CELL, spec())
    assert c["dir"] == (ROOT / FIXTURE).parent
    for key in ("reference", "program", "counts"):
        assert (c["dir"] / f"{c['cfg'][key]}.py").is_file()
    assert (BENCH / "datasets" / f"{c['cfg']['dataset']}.py").is_file()
    assert [m["name"] for m in c["end_to_end"]] == ["setup_s",
                                                    "scenario_rounds_per_s"]
    assert "step_mfu" in [m["name"] for m in c["per_layer"]]


def test_token_configuration_is_correct_through_the_harness(monkeypatch):
    result = run_tiny(monkeypatch, CELL, seed=2**31 + 41,
                      spec=spec())
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "scenario_rounds_per_s"}


def test_token_control_in_bfloat16_is_not_correct():
    c = main.load_cell(CELL, spec())
    cfg = c["cfg"]
    model = main.config_module(c, "reference")
    init = jax.jit(partial(model.init_params, model=cfg["model"]))
    ref = check.Reference(cfg, model, data_mod.make(cfg, 5),
                          lambda: init(jax.random.PRNGKey(5)))
    answers = [dict(ref.answer(s, n, 90 + n, 4, dtype=jnp.bfloat16),
                    scheduler=s, n_clients=n, seed=90 + n)
               for s in ("alg1", "benchmark1", "benchmark2", "oracle")
               for n in (6, 12)]
    ok, found = check.judge(check.compare(ref, answers), cfg["limits"])
    assert not ok, found


def _half_of_each_sequence(monkeypatch):
    from repro.data import ClientBatcher

    sample = ClientBatcher.sample

    def half(self, key):
        return {k: v[..., :v.shape[-1] // 2]
                for k, v in sample(self, key).items()}

    monkeypatch.setattr(ClientBatcher, "sample", half)


def _unchanged_step(monkeypatch):
    from repro.core import aggregation

    monkeypatch.setattr(
        aggregation, "fused_flat_sgd_update",
        lambda g, w, params, opt_state, opt, **kw: (params, opt_state, None))


@pytest.mark.parametrize("fault", [_half_of_each_sequence, _unchanged_step])
def test_token_fault_in_the_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    result = run_tiny(monkeypatch, CELL, seed=2**31 + 43,
                      spec=spec())
    assert result["correct"] is False, result["compared"]
