"""One run of one benchmark cell (``bench/run.py``).

The cell's entry in ``BENCHMARK.json`` names a configuration and a
traffic mix. Everything else is found by name:

* the configuration's file (``BENCHMARK.json`` gives it) — sizes, limits
  of the comparison, the name of its data set and the names of three
  modules beside the file: ``reference`` (the plain model), ``program``
  (which of the program's pieces run it) and ``counts`` (the FLOPs of
  one example's training and forward pass);
* ``bench/datasets/<dataset>.py`` — the data set (:mod:`harness.data`);
* ``bench/traffic/<traffic>.json`` — parameters of the traffic; its
  ``kind`` picks the general driver (:mod:`harness.drivers`);
* ``bench/metrics/<metric>.py`` — one reader per metric, ``read(run)``
  returning the value or None where there is nothing to read.

Set-up makes the configuration's data set (fixed, from its own seed),
the weights from ``--seed``, binds the program's pieces and runs every
shape the window uses. The window then
runs for ``--seconds``; with ``--trace 1`` it is traced and the per-layer
metrics are read from the trace, otherwise the end-to-end ones. After
the window the peak memory is read, the program's state is dropped, and
a sample of the window's answers is compared with the reference. The
last line of standard output is one JSON object (the result).
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import sys
import time
from functools import partial
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
#: JAX's persistent compilation cache, relative to the checkout's root.
CACHE_DIR = ".jax_cache"


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str, spec: dict | None = None) -> dict:
    """The cell's entries in ``spec`` (by default BENCHMARK.json) and the
    files they name, with paths from the checkout's root."""
    if spec is None:
        spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; have "
                         f"{sorted(cells)}")
    cell = cells[workload]
    (entry,) = [c for c in spec["configs"] if c["name"] == cell["config"]]
    end_to_end = [m for m in spec["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    path = ROOT / entry["file"]
    return {"cell": cell, "cfg": json.loads(path.read_text()),
            "dir": path.parent,
            "traffic": json.loads(
                (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()),
            "end_to_end": end_to_end, "per_layer": per_layer}


def config_module(c: dict, key: str):
    """The module that a loaded cell's configuration names under ``key``,
    beside its file."""
    return load_module(c["dir"] / f"{c['cfg'][key]}.py", f"bench_{key}")


class Run:
    """What the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def read_metrics(entries, run) -> dict:
    out = {}
    for i, m in enumerate(entries):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{i}")
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, started: float | None = None) -> int:
    started = time.perf_counter() if started is None else started
    args = parse(argv)
    c = load_cell(args.workload)
    cfg, traffic, cell = c["cfg"], c["traffic"], c["cell"]

    import jax
    import numpy as np

    from harness import check, data as data_mod, device as device_mod
    from harness import trace as trace_mod
    from harness.drivers import DRIVERS

    # JAX's persistent compilation cache lives in the checkout, whatever
    # the environment names, and is not bounded in size: a bound smaller
    # than the programs evicts them and every run compiles again.
    jax.config.update("jax_compilation_cache_dir", str(ROOT / CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        device = device_mod.require_tpu(cell["chips"])
        peaks = device_mod.peaks(device["kind"])
    except device_mod.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    chips = jax.devices()[:cell["chips"]]

    # The data set is the configuration's own, like a downloaded one:
    # the program closes over it, so it is part of every compiled program.
    # --seed draws the weights, the study seeds and the traffic.
    rng = np.random.default_rng(args.seed)
    weights_seed = int(rng.integers(0, 2**31))
    data = data_mod.make(cfg, cfg["data"]["seed"])
    model = config_module(c, "reference")
    program = config_module(c, "program")
    init = jax.jit(partial(model.init_params, model=cfg["model"]))

    def params0():
        return init(jax.random.PRNGKey(weights_seed))

    driver = DRIVERS[traffic["kind"]](cfg, traffic, program.bind(cfg, data),
                                      params0(), rng)
    bound_s = time.perf_counter() - started
    driver.setup()
    compiles = driver.compiles()
    setup_s = time.perf_counter() - started
    print(f"bench: set-up {setup_s:.3f} s, of which {bound_s:.3f} s before "
          f"the warm-up and {compiles} programs compiled or loaded",
          file=sys.stderr)

    record = None
    if args.trace:
        with trace_mod.capture() as traced:
            facts = driver.window(args.seconds)
        record = traced["record"]
    else:
        facts = driver.window(args.seconds)
    compiles = driver.compiles() - compiles
    if compiles:
        print(f"bench: {compiles} compiles inside the window",
              file=sys.stderr)
    device["memory_peak_bytes"] = device_mod.memory_peak_bytes(chips)
    del driver
    gc.collect()

    reference = check.Reference(cfg, model, data, params0)
    answers = check.sample(facts["answers"], traffic["check_answers"],
                           np.random.default_rng([args.seed, 1]))
    checked = time.perf_counter()
    found = check.compare(reference, answers)
    print(f"bench: the reference took {time.perf_counter() - checked:.3f} s",
          file=sys.stderr)
    correct, compared = check.judge(found, cfg["limits"])
    correct = correct and bool(answers)

    run = Run(facts=facts, record=record, cfg=cfg, traffic=traffic,
              counts=config_module(c, "counts"), peaks=peaks,
              chips=len(chips), setup_s=setup_s,
              window_s=(trace_mod.window_s(record) if record
                        else facts["elapsed_s"]))
    result = {"correct": correct, "attempted": facts["attempted"],
              "failed": facts["failed"],
              "metrics": read_metrics(
                  c["per_layer"] if args.trace else c["end_to_end"], run),
              "device": device}
    if record is not None:
        device["busy_s"] = trace_mod.busy_s(record)
        device["window_s"] = trace_mod.window_s(record)
        result["breakdown"] = trace_mod.breakdown(record)
    result["compared"] = compared
    for name, v in compared.items():
        print(f"compared {name}: {v['value']!r} limit {v['limit']!r} "
              f"({len(answers)} answers)", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
