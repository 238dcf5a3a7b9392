"""End-to-end system tests: drivers, simulator, aggregation kernel path."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ClientSimulator, make_quadratic, make_scheduler
from repro.core.energy import DeterministicArrivals
from repro.optim import sgd


def test_train_driver_end_to_end_loss_decreases(tmp_path):
    from repro.launch.train import main
    losses = main([
        "--arch", "stablelm-1.6b", "--reduced", "--steps", "25",
        "--global-batch", "8", "--seq-len", "32", "--n-clients", "4",
        "--scheduler", "alg1", "--arrivals", "periodic",
        "--ckpt-dir", str(tmp_path / "ck"), "--ckpt-every", "10",
    ])
    assert np.mean(losses[-5:]) < losses[0]
    assert any(f.startswith("step_") for f in os.listdir(tmp_path / "ck"))


def test_serve_driver_end_to_end(tmp_path):
    """The serve CLI batches a manifest file plus the demo burst through
    one StudyService: every request answered, demo structure collapsed
    onto a single compile."""
    from repro.experiments import Study
    from repro.launch.serve import main

    manifest = tmp_path / "req.json"
    study = (Study("filed", num_steps=20).axis("scheduler", "alg2")
             .axis("arrivals", "binary").axis("n_clients", 4)
             .axis("seeds", [0, 1]))
    manifest.write_text(study.to_json())

    responses = main([str(manifest), "--demo", "--demo-requests", "4",
                      "--demo-steps", "20"])
    assert len(responses) == 5
    assert all(r.error is None for r in responses)
    by_name = {r.study: r for r in responses}
    assert by_name["filed"].records[0]["scheduler"] == "alg2"
    # All 5 requests ride one dispatch (same steps/seeds/config); the 4
    # demo requests share one structure and the filed study is a second
    # -> exactly two compiles for the whole batch.
    assert responses[0].batch["requests"] == 5
    assert responses[0].cache["compiles"] == 2


@pytest.mark.parametrize("fail", [False, True], ids=["served", "errored"])
def test_serve_cli_exit_code_reflects_errored_responses(monkeypatch, capsys,
                                                        tmp_path, fail):
    """``python -m repro.launch.serve`` exits non-zero when any response
    carries an error, instead of printing ERROR lines and exiting 0."""
    import runpy

    from repro.experiments import engine

    if fail:
        def execute_cells(*args, **kwargs):
            raise RuntimeError("dispatch failed")

        monkeypatch.setattr(engine, "execute_cells", execute_cells)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(sys, "argv", ["serve", "--demo", "--demo-requests",
                                      "2", "--demo-steps", "5"])
    with pytest.raises(SystemExit) as exit_:
        runpy.run_module("repro.launch.serve", run_name="__main__")
    out = capsys.readouterr().out
    assert exit_.value.code == (1 if fail else 0)
    assert ("ERROR RuntimeError: dispatch failed" in out) == fail


def test_simulator_kernel_aggregation_matches_jnp():
    """ClientSimulator with use_kernel=True (Pallas aggregation) must give
    the same trajectory as the pure-jnp path."""
    prob = make_quadratic(jax.random.PRNGKey(0), n_clients=4, dim=8)
    det = DeterministicArrivals.periodic([1, 2, 4, 8], horizon=80)

    def grads_fn(params, key, t):
        return prob.all_grads(params)

    runs = {}
    for use_kernel in (False, True):
        sim = ClientSimulator(
            grads_fn=grads_fn, scheduler=make_scheduler("alg1", 4),
            energy=det, p=prob.p, optimizer=sgd(0.02),
            loss_fn=prob.suboptimality, use_kernel=use_kernel)
        w, hist = sim.run(jax.random.PRNGKey(5), jnp.zeros(8), 60)
        runs[use_kernel] = np.asarray(w)
    np.testing.assert_allclose(runs[False], runs[True], rtol=1e-4, atol=1e-5)


def test_dryrun_machinery_on_tiny_mesh():
    """The dry-run lowering path itself (specs → jit → lower → compile),
    exercised on a 1×1 mesh with a reduced config so it runs in-process."""
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.configs import get_config
    from repro.configs.shapes import InputShape, train_input_specs
    from repro.launch.mesh import make_host_mesh
    from repro.launch.steps import make_train_step
    from repro.models import init_lm
    from repro.sharding import batch_specs, param_specs

    cfg = get_config("qwen2-vl-2b").reduced()
    mesh = make_host_mesh()
    shape = InputShape("tiny", 8, 4, "train")
    with mesh:
        params_s = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
        init_state, train_step = make_train_step(cfg, 2)
        state_s = jax.eval_shape(init_state, params_s)
        st_specs = param_specs(state_s, mesh)
        batch_s, sched_s = train_input_specs(cfg, shape, n_clients=2)
        b_specs = batch_specs(batch_s, mesh)
        ns = lambda t: jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), t,
            is_leaf=lambda x: isinstance(x, PartitionSpec))
        jitted = jax.jit(train_step,
                         in_shardings=(ns(st_specs), ns(b_specs),
                                       None, None))
        lowered = jitted.lower(state_s, batch_s, sched_s["mask"],
                               sched_s["scale"])
        compiled = lowered.compile()
        assert compiled.as_text()  # HLO exists

    from repro.launch.roofline import parse_collective_bytes
    coll = parse_collective_bytes(compiled.as_text())
    assert "total" in coll


def test_roofline_collective_parser():
    from repro.launch.roofline import parse_collective_bytes
    hlo = """
  %ag = bf16[2,1024,512]{2,1,0} all-gather(%x), replica_groups={}
  %ar.1 = f32[256]{0} all-reduce(%y), to_apply=%add
  %rs = f32[128,64]{1,0} reduce-scatter(%z), dimensions={0}
  %cp = u8[4]{0} collective-permute(%w), source_target_pairs={{0,1}}
"""
    got = parse_collective_bytes(hlo)
    assert got["per_kind"]["all-gather"] == 2 * 1024 * 512 * 2
    assert got["per_kind"]["all-reduce"] == 256 * 4
    assert got["per_kind"]["reduce-scatter"] == 128 * 64 * 4
    assert got["per_kind"]["collective-permute"] == 4
    assert got["counts"]["all-gather"] == 1


def test_roofline_model_flops():
    from repro.configs import get_config
    from repro.launch.roofline import active_params, model_flops
    cfg = get_config("phi3.5-moe-42b-a6.6b")
    n_act = active_params(cfg)
    assert 5e9 < n_act < 9e9  # "a6.6b"
    mf = model_flops(cfg, "train_4k")
    np.testing.assert_allclose(mf, 6 * n_act * 256 * 4096, rtol=1e-6)
