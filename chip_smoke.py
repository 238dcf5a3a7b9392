#!/usr/bin/env python3
"""Smoke test of the main path on a TPU, at the paper CNN's full width.

    python3 chip_smoke.py             # one chip: the train and serve phases
    python3 chip_smoke.py --chips 4   # a 2x2 host: the client-sharded phase

Phases, each printing one line of facts:

* ``train`` — the ``fig1`` study (4 schedulers on periodic energy) with
  the paper CNN at 32x32x3, N=40 clients in 4 groups, tau=(1,5,10,20),
  batch 16, ``sgd(0.05)``, run through ``Study.run``; its client
  gradients arrive as the CNN's parameter tree, which the server update
  reduces leaf by leaf: losses finite and falling from their round-0
  value. The same study over its first rounds, with the gradients
  raveled into one ``(N, P)`` array so that the fused Pallas server
  update runs (``use_kernel=True``), must agree with the leafwise run to
  f32 tolerance, and its lowered program must call a ``tpu_custom_call``
  (Mosaic, not the Pallas interpreter).
* ``serve`` — a ``StudyService`` over the same model context answers
  mixed-population manifests with one compile, and a resubmission of
  the same manifests with none.
* ``sharded`` (``--chips 4`` only) — the train study over its first
  rounds, client-sharded over ``make_client_mesh(4)`` with the ``psum``,
  ``fused`` and ``gather`` reductions, against the unsharded vmap run:
  psum/fused within f32 tolerance, gather bitwise.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``,
printed only when every phase passed. Without a TPU (for example under
``JAX_PLATFORMS=cpu``), or without the repository's ``src/`` next to
this file, the script exits non-zero before any phase. JAX's persistent
compile cache goes to ``$JAX_COMPILATION_CACHE_DIR`` when set, else to
``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent

# The paper's Fig-1 setup at full width (examples/paper_cifar.py --full).
N_CLIENTS, N_GROUPS, TAUS = 40, 4, (1, 5, 10, 20)
IMAGE_HW, BATCH, LR = 32, 16, 0.05
N_TRAIN, N_EVAL = 8000, 512
SEED = 0
ROUNDS = 24
#: Rounds of the runs compared with each other. The f32 tolerance is a
#: per-step contract (DESIGN.md §9); over 24 rounds the CNN's trajectory
#: amplifies a 1-ulp difference of one step far past it (a reassociated
#: client sum drifted 0.049 in held-out loss by round 24 at full width).
#: Two rounds check one update from the shared start and one from each
#: run's own carry.
COMPARE_ROUNDS = 2
SERVE_POPULATIONS = (10, 20, 30, 40)
SERVE_ROUNDS = 8
#: Chips of the client-sharded phase (a 2x2 host).
SHARDED_CHIPS = 4
#: f32 tolerance of the kernel path against the leafwise jnp path
#: (tests/test_fused_update.py, tests/test_aggregation_flat.py).
RTOL = ATOL = 1e-5


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def require_tpu(n_chips: int) -> dict:
    """The device record of the last line; fails unless JAX runs on a TPU
    with at least ``n_chips`` chips."""
    devices = jax.devices()
    check(devices[0].platform == "tpu",
          f"no TPU: JAX runs on {devices[0].platform!r}")
    check(len(devices) >= n_chips,
          f"--chips {n_chips} needs {n_chips} chips, JAX has {len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def mosaic_in(hlo_text: str) -> bool:
    """Whether a lowered program calls a Mosaic-compiled Pallas kernel."""
    return "tpu_custom_call" in hlo_text


def fig1_context():
    """Simulator ingredients (``grads_fn``, ``p``, ``optimizer``,
    ``loss_fn``) and initial params of the paper's Fig-1 run; the loss is
    the CNN's cross-entropy on a fixed held-out batch."""
    import jax.numpy as jnp

    from examples.paper_cifar import per_client_grads_fn
    from repro.data import (
        ClientBatcher,
        group_label_skew_partition,
        make_confusable_image_classification,
    )
    from repro.models.cnn import cnn_loss, init_cnn
    from repro.optim import sgd

    ds = make_confusable_image_classification(
        SEED, N_TRAIN + N_EVAL, image_shape=(IMAGE_HW, IMAGE_HW, 3),
        similarity=0.9, noise=0.8)
    train_x, train_y = ds.images[:N_TRAIN], ds.labels[:N_TRAIN]
    eval_x = jnp.asarray(ds.images[N_TRAIN:])
    eval_y = jnp.asarray(ds.labels[N_TRAIN:])
    parts = group_label_skew_partition(SEED, train_y, N_CLIENTS, N_GROUPS,
                                       skew=1.0)
    batcher = ClientBatcher([{"x": train_x[ix], "y": train_y[ix]}
                             for ix in parts], batch_size=BATCH, seed=SEED)
    ctx = {"grads_fn": per_client_grads_fn(batcher, IMAGE_HW),
           "p": batcher.p, "optimizer": sgd(LR),
           "loss_fn": lambda params: cnn_loss(params, eval_x, eval_y)}
    return ctx, init_cnn(jax.random.PRNGKey(SEED), image_hw=IMAGE_HW)


def flat_gradients(ctx) -> dict:
    """``ctx`` whose ``grads_fn`` ravels the client-stacked gradient tree
    into one ``(N, P)`` array: gradients that arrive flat take the fused
    Pallas update."""
    from repro.core.aggregation import ravel_stacked

    grads_fn = ctx["grads_fn"]
    return {**ctx, "grads_fn": lambda params, key, t: ravel_stacked(
        grads_fn(params, key, t))}


def fig1_study(rounds: int):
    from repro.experiments import get_study

    return get_study("fig1", n_clients=N_CLIENTS, num_steps=rounds,
                     taus_profile=list(TAUS), seeds=[SEED + 1])


def timed_run(study, **kw):
    """``study.run(**kw)`` with every result on the host; returns
    ``(cells, seconds)``."""
    t0 = time.perf_counter()
    result = study.run(**kw)
    cells = {name: jax.tree_util.tree_map(np.asarray, result[name])
             for name in result}
    return cells, time.perf_counter() - t0


def leaves(tree) -> list:
    return jax.tree_util.tree_leaves(tree)


def check_losses(cells, *, falling: bool = True) -> dict:
    """Every cell's losses finite; with ``falling``, in every cell whose
    server stepped in at least half the rounds, the final loss below the
    round-0 one. (Wait-for-all steps only at t=0 and t=20 on this energy
    profile, and one SGD step need not lower a held-out loss.) Returns
    ``{cell: [round-0 loss, final loss]}`` of the first seed."""
    out = {}
    for name, cell in cells.items():
        loss = cell.history.loss
        check(np.all(np.isfinite(loss)), f"{name}: non-finite loss")
        stepped = np.mean(cell.history.weight_sum > 0, axis=-1)
        check(not falling
              or np.all((loss[:, -1] < loss[:, 0]) | (stepped < 0.5)),
              f"{name}: loss did not fall from round 0 "
              f"({loss[:, 0]} -> {loss[:, -1]})")
        out[name] = [float(loss[0, 0]), float(loss[0, -1])]
    return out


def compare(ref, cells, *, bitwise: bool) -> dict:
    """Final params and losses of ``cells`` against ``ref``: whether they
    are bitwise equal, or within the f32 tolerance, and by how much they
    differ at most."""
    pairs = [(x, y) for name in ref
             for x, y in zip(leaves((ref[name].params,
                                     ref[name].history.loss)),
                             leaves((cells[name].params,
                                     cells[name].history.loss)))]
    if bitwise:
        ok = all(np.array_equal(x, y) for x, y in pairs)
    else:
        ok = all(np.allclose(x, y, rtol=RTOL, atol=ATOL) for x, y in pairs)
    return {"ok": ok, "bitwise": bitwise,
            "max_abs_diff": max(float(np.max(np.abs(x - y)))
                                for x, y in pairs)}


def phase_train(ctx, params0) -> dict:
    study = fig1_study(ROUNDS)
    cells, cold = timed_run(study, params0=params0, use_kernel=True, **ctx)
    _, warm = timed_run(study, params0=params0, use_kernel=True, **ctx)
    short = fig1_study(COMPARE_ROUNDS)
    flat = flat_gradients(ctx)
    kern_short, kern_s = timed_run(short, params0=params0, use_kernel=True,
                                   **flat)
    ref, _ = timed_run(short, params0=params0, use_kernel=True, **ctx)

    sim = study.simulator(use_kernel=True, **flat)
    scheduler, energy = study.resolve()[0].build()
    hlo = jax.jit(lambda key, params: sim.run(
        key, params, 1, scheduler=scheduler, energy=energy)).lower(
        jax.random.PRNGKey(SEED), params0).as_text()
    return {"cells": len(cells), "rounds": ROUNDS, "clients": N_CLIENTS,
            "n_params": sum(x.size for x in leaves(params0)),
            "cold_s": cold, "warm_s": warm, "compile_s": cold - warm,
            "loss_round0_final": check_losses(cells),
            "compare_rounds": COMPARE_ROUNDS, "kernel_cold_s": kern_s,
            "kernel_loss": check_losses(kern_short, falling=False),
            "kernel_vs_leafwise": compare(ref, kern_short, bitwise=False),
            "rtol": RTOL, "atol": ATOL, "tpu_custom_call": mosaic_in(hlo)}


def check_train(facts: dict) -> None:
    diff = facts["kernel_vs_leafwise"]["max_abs_diff"]
    check(facts["kernel_vs_leafwise"]["ok"], f"kernel and leafwise runs "
          f"differ by up to {diff} beyond rtol={RTOL} atol={ATOL}")
    check(facts["tpu_custom_call"],
          "kernel run lowered without a tpu_custom_call")


def phase_serve(ctx, params0) -> dict:
    from repro.experiments import Study
    from repro.serve import StudyService

    service = StudyService(params0=params0, use_kernel=True, **ctx)
    manifests = [Study(f"smoke_n{n}", num_steps=SERVE_ROUNDS)
                 .axis("scheduler", "alg1").axis("arrivals", "periodic")
                 .axis("n_clients", n).axis("seeds", [SEED]).to_json()
                 for n in SERVE_POPULATIONS]

    def serve_all():
        t0 = time.perf_counter()
        for m in manifests:
            service.submit(m)
        responses = service.flush()
        secs = time.perf_counter() - t0
        check(len(responses) == len(manifests),
              f"{len(responses)} responses to {len(manifests)} requests")
        for r in responses:
            check(r.error is None, f"{r.study}: {r.error}")
            for cell in r.result.values():
                check(np.all(np.isfinite(np.asarray(cell.history.loss))),
                      f"{r.study}: non-finite loss")
        return secs

    first_s = serve_all()
    compiles = service.stats()["compiles"]
    again_s = serve_all()
    return {"requests": len(manifests), "populations": SERVE_POPULATIONS,
            "rounds": SERVE_ROUNDS, "compiles": compiles,
            "resubmit_compiles": service.stats()["compiles"] - compiles,
            "first_batch_s": first_s, "resubmit_batch_s": again_s}


def check_serve(facts: dict) -> None:
    check(facts["compiles"] == 1,
          f"first batch compiled {facts['compiles']} times, not once")
    check(facts["resubmit_compiles"] == 0,
          f"resubmission compiled {facts['resubmit_compiles']} times")


def _peak_bytes(device) -> int:
    stats = device.memory_stats()
    check(stats is not None, f"{device} reports no memory stats")
    return int(stats["peak_bytes_in_use"])


def phase_sharded(ctx, params0) -> dict:
    from repro.experiments import ExecutionConfig, make_client_mesh

    mesh = make_client_mesh(SHARDED_CHIPS)
    devices = list(mesh.devices.flat)
    study = fig1_study(COMPARE_ROUNDS)
    runs, secs = {}, {}
    for reduction in ("psum", "fused", "gather"):
        runs[reduction], secs[reduction] = timed_run(
            study, params0=params0, use_kernel=True, **ctx,
            config=ExecutionConfig(mesh=mesh, client_reduction=reduction))
    # Read before the unsharded run, which uses device 0 only.
    peaks = [_peak_bytes(d) for d in devices]
    ref, secs["vmap"] = timed_run(study, params0=params0, use_kernel=True,
                                  **ctx)
    for cells in runs.values():
        check_losses(cells, falling=False)
    return {"devices": [d.id for d in devices], "rounds": COMPARE_ROUNDS,
            "clients": N_CLIENTS, "peak_bytes": peaks,
            "shard_bytes": N_CLIENTS // len(devices) * sum(
                x.size * x.dtype.itemsize for x in leaves(params0)),
            "wall_s": secs,
            "loss_round0_final": check_losses(ref, falling=False),
            "vs_vmap": {r: compare(ref, runs[r], bitwise=(r == "gather"))
                        for r in runs},
            "rtol": RTOL, "atol": ATOL}


def check_sharded(facts: dict) -> None:
    ids = facts["devices"]
    check(len(ids) == SHARDED_CHIPS and len(set(ids)) == SHARDED_CHIPS,
          f"client mesh spans devices {ids}, not {SHARDED_CHIPS} distinct "
          f"ones")
    # Each device held at least its (N/4, P) gradient shard: a layout that
    # left the work on device 0 would leave the others' peaks low.
    check(all(p >= facts["shard_bytes"] for p in facts["peak_bytes"]),
          f"device peaks {facts['peak_bytes']} below one gradient shard "
          f"({facts['shard_bytes']} B)")
    for reduction, result in facts["vs_vmap"].items():
        check(result["ok"], f"{reduction} vs vmap differ by up to "
              f"{result['max_abs_diff']}: " + (
                  "not bitwise" if result["bitwise"] else
                  f"beyond rtol={RTOL} atol={ATOL}"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, SHARDED_CHIPS),
                    default=1,
                    help="4: run only the client-sharded phase on a 2x2 host")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}; "
              f"run it from a checkout of the repository", file=sys.stderr)
        return 2
    for path in (str(ROOT / "src"), str(ROOT)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from repro._env import enable_compilation_cache

    enable_compilation_cache()
    try:
        device = require_tpu(args.chips)
        ctx, params0 = fig1_context()
        phases = ([("sharded", phase_sharded, check_sharded)]
                  if args.chips == SHARDED_CHIPS else
                  [("train", phase_train, check_train),
                   ("serve", phase_serve, check_serve)])
        for name, phase, check_phase in phases:
            facts = phase(ctx, params0)
            print(name, json.dumps(facts, default=str), flush=True)
            check_phase(facts)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
