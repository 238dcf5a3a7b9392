"""Multi-host grid execution: ``jax.distributed`` init + worker CLI.

The process-spanning rung of the scaling ladder (DESIGN.md §13,
ROADMAP "Multi-host grids"). Three layers, top to bottom:

1. :func:`initialize` / :func:`init_from_env` — bring up the
   ``jax.distributed`` runtime from CLI flags or the ``REPRO_DIST_*``
   environment (``repro._env.distributed_env``). Must run before the
   jax backend initializes; on CPU hosts it selects the ``gloo``
   cross-process collectives and the placeholder device count first.
2. The worker CLI (``python -m repro.launch.distributed``) — runs the
   canonical differential job (a ragged Fig-1 sub-grid on the quadratic
   problem, ≥ 2 schedulers × ragged populations) through the *unchanged*
   ``Study.run`` / ``execute_cells`` dispatch on a process-spanning
   mesh, asserts the one-compile-per-structure-group guarantee, and
   writes results + a per-process report.
3. :func:`launch_simulated` — the CI story: spawn N copies of this CLI
   as subprocesses on one machine, each pinned to its own slice of CPU
   placeholder devices (the ``repro._env`` template, same subprocess
   trick as the SIGKILL suites), coordinated over localhost. No
   accelerators required; ``--simulate N`` does the same from the
   command line.

Real two-host launch (see README)::

    # host A (coordinator)               # host B
    python -m repro.launch.distributed \\
        --coordinator hostA:9876 \\
        --num-processes 2 --process-id 0  # ... --process-id 1

This module keeps its top level jax-free: workers import it, configure
the environment, and only then let jax in.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import socket
import subprocess
import sys
import time

from repro._env import (
    DIST_COORDINATOR,
    DIST_LOCAL_DEVICES,
    DIST_NUM_PROCESSES,
    DIST_PROCESS_ID,
    distributed_env,
    ensure_host_device_count,
)

#: src/ directory containing the ``repro`` package — what workers need
#: on PYTHONPATH (``repro`` is a namespace package; __file__ works).
_SRC = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_DEVICE_COUNT_FLAG = re.compile(
    r"--xla_force_host_platform_device_count=\d+\s*")


def initialize(coordinator: str, num_processes: int, process_id: int,
               local_devices: int | None = None) -> None:
    """Bring up the ``jax.distributed`` runtime for this process.

    Order matters and is owned here so callers can't get it wrong:
    placeholder-device count first (XLA client flags are read at first
    jax import), then the CPU cross-process collectives implementation
    (fixed at backend initialization — stock CPU jaxlib otherwise
    refuses multi-process computations outright), then
    ``jax.distributed.initialize``. A ``num_processes == 1`` call is a
    no-op beyond the device-count flag, so single-host drivers can share
    the code path.
    """
    if local_devices is not None:
        ensure_host_device_count(local_devices)
    if num_processes <= 1:
        return
    import jax

    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):  # a jax without the option
        pass
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def init_from_env() -> bool:
    """Initialize from the ``REPRO_DIST_*`` environment if present.

    Returns True when a distributed runtime was brought up, False when
    the environment carries no distributed configuration (single-process
    session). Partial configuration raises (see
    :func:`repro._env.distributed_env`).
    """
    cfg = distributed_env()
    if cfg is None:
        return False
    initialize(cfg["coordinator"], cfg["num_processes"], cfg["process_id"],
               local_devices=cfg["local_devices"])
    return cfg["num_processes"] > 1


# ------------------------------------------------------ canonical job

#: Fixed shape of the differential job: capacity-8 quadratic population,
#: two scheduler structures, ragged cells (DESIGN.md §13).
JOB_N_CAP, JOB_DIM = 8, 5
JOB_SCHEDULERS = ("alg1", "benchmark1")
JOB_POPULATIONS = (5, 8)  # ragged: one below-capacity cell per structure


def make_job_sim():
    """The job's ClientSimulator — deterministic gradients and the
    elementwise-plus-one-sum loss that is bit-stable under vmap (the
    same recipe as the client-sharding bitwise suite), so gather-mode
    multi-process runs can be held to *bitwise* equality with the
    single-process vmap engine."""
    import jax
    import jax.numpy as jnp

    from repro.core import ClientSimulator, make_quadratic
    from repro.optim import sgd

    master = make_quadratic(jax.random.PRNGKey(2), n_clients=JOB_N_CAP,
                            dim=JOB_DIM, hetero=1.0)
    w_star = master.w_star
    return ClientSimulator(
        grads_fn=lambda w, k, t: master.all_grads(w),
        p=master.p, optimizer=sgd(0.02),
        loss_fn=lambda w: jnp.sum((w - w_star) ** 2))


def make_job_study(num_steps: int = 25, seeds: int = 2):
    """Ragged Fig-1 sub-grid: 2 scheduler structures × ragged
    populations × seeds — 2 structure groups, every group ragged."""
    from repro.experiments import Study

    return (Study("multihost_fig1", num_steps=num_steps)
            .axis("scheduler", list(JOB_SCHEDULERS))
            .axis("arrivals", "periodic")
            .axis("n_clients", list(JOB_POPULATIONS))
            .axis("seeds", seeds))


def job_params0():
    import jax.numpy as jnp

    return jnp.full((JOB_DIM,), 4.0)


def flatten_results(tag: str, results) -> dict:
    """``{tag|cell|leafpath: np.ndarray}`` — the npz layout both the
    workers and the comparing test/bench build, so equality checks are
    plain key-wise array comparisons."""
    import numpy as np

    flat = {}
    for cell, res in results.items():
        fields = {"params": res.params, "loss": res.history.loss,
                  "participation": res.history.participation,
                  "weight_sum": res.history.weight_sum,
                  "finite": res.history.finite, "diverged": res.diverged}
        for field, leaf in fields.items():
            if leaf is not None:
                flat[f"{tag}|{cell}|{field}"] = np.asarray(leaf)
    return flat


def reference_results(num_steps: int = 25, seeds: int = 2):
    """The single-process vmap-engine oracle for the canonical job."""
    study = make_job_study(num_steps, seeds)
    return study.run(sim=make_job_sim(), params0=job_params0()).cells


# ------------------------------------------------------- worker body

def _build_mesh(kind: str):
    from repro.experiments import placement

    if kind == "clients":
        return placement.make_client_mesh()
    if kind == "multihost":
        return placement.make_multihost_mesh()
    if kind == "cells":
        return placement.make_cell_mesh()
    raise ValueError(f"unknown mesh kind {kind!r} "
                     "(have clients, multihost, cells)")


def run_worker(args) -> dict:
    """Execute the canonical job on this (possibly multi-)process.

    One pass per (mesh, reduction) combo: dispatch through the unchanged
    ``Study.run``, assert the trace-count guarantee (one
    ``_run_group_sharded`` compile per structure group per process,
    zero on the warm repeat), optionally time warm dispatches, and
    collect everything into the report dict. Process 0 additionally
    saves the flattened results npz.
    """
    import jax
    import numpy as np

    from repro.experiments import ExecutionConfig, engine, placement

    sim = make_job_sim()
    study = make_job_study(args.steps, args.seeds)
    params0 = job_params0()
    _, _, groups = engine.resolve_structure_groups(study.resolve(), sim=sim)
    n_groups = len(groups)
    report = {
        "process_id": jax.process_index(),
        "process_count": jax.process_count(),
        "global_devices": jax.device_count(),
        "local_devices": jax.local_device_count(),
        "combos": {},
    }
    flat_all = {}
    for kind in args.mesh.split(","):
        mesh = _build_mesh(kind)
        spans = placement.mesh_process_count(mesh)
        for reduction in args.reduction.split(","):
            tag = f"{kind}-{reduction}"
            cfg = ExecutionConfig(mesh=mesh, client_reduction=reduction)
            before = placement._run_group_sharded._cache_size()
            result = study.run(sim=sim, params0=params0, config=cfg)
            compiles = placement._run_group_sharded._cache_size() - before
            if compiles != n_groups:
                raise AssertionError(
                    f"{tag}: expected one compile per structure group "
                    f"({n_groups}), traced {compiles}")
            study.run(sim=sim, params0=params0, config=cfg)
            warm = placement._run_group_sharded._cache_size() - before
            if warm != n_groups:
                raise AssertionError(
                    f"{tag}: warm repeat recompiled ({warm - n_groups} "
                    "new traces)")
            timing_us = None
            if args.timing_iters > 0:
                t0 = time.perf_counter()
                for _ in range(args.timing_iters):
                    study.run(sim=sim, params0=params0, config=cfg)
                timing_us = (time.perf_counter() - t0) / args.timing_iters \
                    * 1e6
            report["combos"][tag] = {
                "mesh_shape": dict(mesh.shape),
                "mesh_process_span": spans,
                "compiles": compiles,
                "warm_new_compiles": warm - n_groups,
                "dispatch_us": timing_us,
                "us_per_step": (timing_us / args.steps
                                if timing_us is not None else None),
            }
            flat_all.update(flatten_results(tag, result.cells))
    if args.out and jax.process_index() == 0:
        os.makedirs(args.out, exist_ok=True)
        np.savez(os.path.join(args.out, "results.npz"), **flat_all)
    if args.out:
        path = os.path.join(args.out,
                            f"report_p{jax.process_index()}.json")
        os.makedirs(args.out, exist_ok=True)
        with open(path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
    return report


# ------------------------------------------------- simulated harness

def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_simulated(num_processes: int = 2, local_devices: int = 4, *,
                     argv=(), timeout: float = 600.0,
                     ) -> list[subprocess.CompletedProcess]:
    """Run ``num_processes`` copies of the worker CLI on this machine.

    Each worker is a fresh interpreter pinned to its own
    ``local_devices`` CPU placeholder devices and coordinated over a
    fresh localhost port — the simulated multi-host CI story
    (DESIGN.md §13). The parent's own XLA device-count flag is stripped
    from the children's environment so the per-worker pin always wins
    (the parent test/bench session typically forced 8 devices already),
    and ``JAX_PLATFORMS=cpu`` is set explicitly: this is a CPU-only
    harness, and on a machine with a chip a worker must not contend
    with the parent (or its siblings) for it.
    Returns the completed processes in process-id order; raises if any
    worker exits non-zero (its stderr in the message) or hangs past
    ``timeout``.
    """
    port = _free_port()
    procs = []
    for pid in range(num_processes):
        env = dict(os.environ)
        env[DIST_COORDINATOR] = f"127.0.0.1:{port}"
        env[DIST_NUM_PROCESSES] = str(num_processes)
        env[DIST_PROCESS_ID] = str(pid)
        env[DIST_LOCAL_DEVICES] = str(local_devices)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = _DEVICE_COUNT_FLAG.sub(
            "", env.get("XLA_FLAGS", "")).strip()
        env["PYTHONPATH"] = _SRC + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.distributed", *argv],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    done, deadline = [], time.monotonic() + timeout
    try:
        for pid, proc in enumerate(procs):
            left = max(1.0, deadline - time.monotonic())
            out, err = proc.communicate(timeout=left)
            done.append(subprocess.CompletedProcess(
                proc.args, proc.returncode, out, err))
    except subprocess.TimeoutExpired:
        for proc in procs:
            proc.kill()
        raise
    bad = [(i, p) for i, p in enumerate(done) if p.returncode != 0]
    if bad:
        i, p = bad[0]
        raise RuntimeError(
            f"simulated worker {i}/{num_processes} exited "
            f"{p.returncode}:\n{p.stderr[-4000:]}")
    return done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.distributed",
        description="multi-host grid worker / simulated-multihost driver")
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (or REPRO_DIST_COORDINATOR)")
    ap.add_argument("--num-processes", type=int, default=None)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--local-devices", type=int, default=None,
                    help="CPU placeholder devices for this process")
    ap.add_argument("--simulate", type=int, default=0, metavar="N",
                    help="spawn N local workers instead of being one")
    ap.add_argument("--mesh", default="clients",
                    help="comma list of clients|multihost|cells")
    ap.add_argument("--reduction", default="gather",
                    help="comma list of gather|psum|... client reductions")
    ap.add_argument("--steps", type=int, default=25)
    ap.add_argument("--seeds", type=int, default=2)
    ap.add_argument("--timing-iters", type=int, default=0)
    ap.add_argument("--out", default="",
                    help="directory for results.npz + report_p*.json")
    args = ap.parse_args(argv)

    if args.simulate:
        passthrough = ["--mesh", args.mesh, "--reduction", args.reduction,
                       "--steps", str(args.steps),
                       "--seeds", str(args.seeds),
                       "--timing-iters", str(args.timing_iters)]
        if args.out:
            passthrough += ["--out", args.out]
        results = launch_simulated(
            args.simulate, args.local_devices or 4, argv=passthrough)
        for proc in results:
            sys.stdout.write(proc.stdout)
        print(f"simulated {args.simulate}-process run complete")
        return 0

    if args.coordinator is not None:
        initialize(args.coordinator, args.num_processes or 1,
                   args.process_id or 0, local_devices=args.local_devices)
    elif not init_from_env() and args.local_devices:
        ensure_host_device_count(args.local_devices)

    report = run_worker(args)
    print(json.dumps(report, indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
