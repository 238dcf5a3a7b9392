"""Helpers of the benchmark's CPU tests: paths, and a run of the harness
at a tiny size with the look for a chip skipped."""

from __future__ import annotations

import contextlib
import io
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (BENCH, ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from harness import main as _harness  # noqa: E402

#: ``load_cell`` as the harness has it, whatever a test patches.
LOAD_CELL = _harness.load_cell


#: Sizes of a CPU test, set where a configuration has the key: 8x8
#: images, 12 clients (data weights that bfloat16 cannot hold exactly, as
#: at full size), batches of 4; widths as given. The learning rate is
#: raised so that one round moves the small CNN's held-out loss about as
#: far as at full size.
TINY = {"n_clients": 12, "batch_size": 4, "lr": 0.5}
TINY_MODEL = {"image_hw": 8}
TINY_DATA = {"n_train": 192, "n_eval": 40, "n_loss": 32}


def shrink(c: dict) -> dict:
    """A loaded cell (``harness.main.load_cell``) cut to a CPU test's size
    (:data:`TINY`), only in the keys its configuration has, with a few
    rounds and seeds a run and a few answers compared."""
    cfg = c["cfg"]
    for have, sizes in ((cfg, TINY), (cfg["model"], TINY_MODEL),
                        (cfg["data"], TINY_DATA)):
        have.update((k, v) for k, v in sizes.items() if k in have)
    c["traffic"].update(num_steps=4, eval_every=4, seeds_per_run=2,
                        check_answers=4)
    return c


def run_tiny(monkeypatch, workload: str, seed: int, seconds: float = 2.0,
             trace: int = 0, edit=lambda c: c,
             spec: dict | None = None) -> dict:
    """Run ``bench/run.py``'s main at a tiny size on the CPU and return
    its result line; the chip check is skipped and JAX's configuration
    (the compile cache) left as it is. ``edit`` may change the loaded
    cell before :func:`shrink` cuts it; ``spec`` stands in for
    ``BENCHMARK.json``."""
    import jax

    from harness import device, main

    monkeypatch.setattr(main, "load_cell",
                        lambda w: shrink(edit(LOAD_CELL(w, spec))))
    monkeypatch.setattr(device, "require_tpu", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    peaks = json.loads(device.PEAKS.read_text())["devices"]["TPU v5 lite"]
    monkeypatch.setattr(device, "peaks", lambda kind: peaks)
    monkeypatch.setattr(device, "memory_peak_bytes", lambda devs: 0)
    out = io.StringIO()
    with monkeypatch.context() as m, contextlib.redirect_stdout(out):
        m.setattr(jax.config, "update", lambda *a: None)
        rc = main.main(["--workload", workload, "--seed", str(seed),
                        "--seconds", str(seconds), "--trace", str(trace)])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def with_traffic(name: str):
    """An ``edit`` for :func:`run_tiny` that runs the cell under the
    traffic file ``bench/traffic/<name>.json``."""

    def edit(c):
        c["traffic"] = json.loads((BENCH / "traffic" / f"{name}.json")
                                  .read_text())
        return c

    return edit
