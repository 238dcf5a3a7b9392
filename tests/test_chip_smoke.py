"""``chip_smoke.py`` rehearsed on the CPU.

The script refuses to run without a TPU, so the rehearsal stubs its
platform check (and the Mosaic mark, which the Pallas interpreter never
lowers) inside the test and shrinks the Fig-1 sizes; every phase, check
and the last line's shape are otherwise the script's own.
"""

import importlib.util
import json
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(N_CLIENTS=4, IMAGE_HW=8, BATCH=2, N_TRAIN=200, N_EVAL=32,
            SERVE_POPULATIONS=(2, 3, 4), SERVE_ROUNDS=3)


@pytest.fixture
def smoke(monkeypatch, tmp_path):
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    # A cache directory from the environment leaves the session's jax
    # config untouched.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    return module


def test_rehearsal_runs_train_and_serve_phases(smoke, monkeypatch, capsys):
    device = {"platform": "tpu", "kind": "cpu rehearsal", "count": 1}
    monkeypatch.setattr(smoke, "require_tpu", lambda n_chips: device)
    monkeypatch.setattr(smoke, "mosaic_in", lambda hlo_text: True)
    for name, value in TINY.items():
        monkeypatch.setattr(smoke, name, value)

    assert smoke.main([]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    phases = {line.split(" ", 1)[0]: json.loads(line.split(" ", 1)[1])
              for line in lines[:-1]}
    assert list(phases) == ["train", "serve"]
    train, serve = phases["train"], phases["serve"]
    assert train["cells"] == 4 and train["rounds"] == smoke.ROUNDS
    assert train["compare_rounds"] == smoke.COMPARE_ROUNDS
    assert train["kernel_vs_leafwise"]["ok"] and train["tpu_custom_call"]
    for name, (round0, final) in train["loss_round0_final"].items():
        assert final < round0 or name.startswith("benchmark2")
    assert serve["compiles"] == 1 and serve["resubmit_compiles"] == 0
    assert serve["requests"] == len(TINY["SERVE_POPULATIONS"])
    assert json.loads(lines[-1]) == {"ok": True, "device": device}


@pytest.mark.multidevice
def test_rehearsal_runs_the_four_chip_sharded_phase(smoke, monkeypatch,
                                                    capsys):
    """``--chips 4`` on four of the session's virtual CPU devices, with the
    paper CNN swapped for a quadratic model.

    XLA:CPU compiles the CNN's convolutions differently in the sharded
    and the unsharded program (its multi-threaded Eigen contractions
    split the work by thread, and fusion differs), so there the CNN's
    per-client gradients differ by up to 1.2e-7 before any reduction
    and gather cannot be bitwise. The quadratic's gradients come out
    identical in both programs, which leaves the phase's own mesh,
    reductions, checks and last line under test.
    """
    import jax
    import jax.numpy as jnp

    from repro.core import make_quadratic
    from repro.optim import sgd

    if jax.device_count() < smoke.SHARDED_CHIPS:
        pytest.skip("needs 4 devices")
    n = 8
    quad = make_quadratic(jax.random.PRNGKey(2), n_clients=n, dim=5)
    ctx = {"grads_fn": lambda w, key, t: quad.all_grads(w), "p": quad.p,
           "optimizer": sgd(0.02),
           "loss_fn": lambda w: jnp.sum((w - quad.w_star) ** 2)}
    device = {"platform": "tpu", "kind": "cpu rehearsal", "count": 4}
    monkeypatch.setattr(smoke, "require_tpu", lambda n_chips: device)
    monkeypatch.setattr(smoke, "fig1_context",
                        lambda: (ctx, jnp.full((5,), 4.0)))
    # CPU devices report no memory statistics.
    monkeypatch.setattr(smoke, "_peak_bytes", lambda d: 1 << 40)
    monkeypatch.setattr(smoke, "N_CLIENTS", n)

    assert smoke.main(["--chips", "4"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert len(lines) == 2 and lines[0].startswith("sharded ")
    facts = json.loads(lines[0].split(" ", 1)[1])
    assert len(set(facts["devices"])) == 4
    assert facts["rounds"] == smoke.COMPARE_ROUNDS
    assert set(facts["vs_vmap"]) == {"psum", "fused", "gather"}
    assert facts["vs_vmap"]["gather"] == {"ok": True, "bitwise": True,
                                          "max_abs_diff": 0.0}
    assert all(r["ok"] for r in facts["vs_vmap"].values())
    assert json.loads(lines[-1]) == {"ok": True, "device": device}


def test_without_a_tpu_exits_nonzero_with_no_ok_line(smoke, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == ""
    assert "no TPU" in out.err


def test_outside_a_checkout_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
