"""Process-environment helpers: the persistent compile cache and the
CPU-only simulated multi-process harness."""

import json
import os
import subprocess
import sys
from pathlib import Path

from repro._env import COMPILATION_CACHE_ENV, DEFAULT_COMPILATION_CACHE
from repro.launch import distributed as dist

ROOT = Path(__file__).resolve().parents[1]
PROBE = ("import json, jax; from repro._env import enable_compilation_cache;"
         " print(json.dumps([enable_compilation_cache(),"
         " jax.config.jax_compilation_cache_dir]))")


def _probe(**env_overrides):
    env = {k: v for k, v in os.environ.items() if k != COMPILATION_CACHE_ENV}
    env.update(env_overrides)
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run([sys.executable, "-c", PROBE], env=env,
                          capture_output=True, text=True, timeout=120,
                          check=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compilation_cache_honours_the_environment(tmp_path):
    where = str(tmp_path / "cache")
    assert _probe(**{COMPILATION_CACHE_ENV: where}) == [where, where]


def test_compilation_cache_defaults_to_a_fixed_checkout_path():
    expected = str(ROOT / ".jax_cache")
    assert str(DEFAULT_COMPILATION_CACHE) == expected
    assert _probe() == [expected, expected]


def test_simulated_workers_are_pinned_to_the_cpu(monkeypatch):
    """The harness is CPU-only: even under a parent that asked for an
    accelerator, no worker may reach for the chip."""
    seen = []

    class FakeWorker:
        def __init__(self, args, env, **kw):
            seen.append(env)
            self.args, self.returncode = args, 0

        def communicate(self, timeout=None):
            return "", ""

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(dist.subprocess, "Popen", FakeWorker)
    done = dist.launch_simulated(3, local_devices=2)
    assert len(done) == 3
    assert [env["JAX_PLATFORMS"] for env in seen] == ["cpu"] * 3
