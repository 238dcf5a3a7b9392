"""The general drivers of traffic, each read from a traffic file's
parameters (``kind`` names the driver):

* ``study_runs`` — whole runs of a named study, back to back, each with
  its own seeds; results pulled to the host inside the window, as users
  get them.

A driver does its own set-up (``setup``) and then its window (``window``);
both return plain facts the harness turns into metrics. Every shape the
window uses is run once in set-up.
"""

from __future__ import annotations

import sys
import time

import numpy as np

from harness import traffic as traffic_mod


class StudyRuns:
    kind = "study_runs"

    def __init__(self, cfg, traffic, bound, params0, rng):
        from repro.experiments import ExecutionConfig, Study

        self.traffic = traffic
        self.bound = bound
        self.params0 = params0
        self.rng = rng
        self.study = Study("bench", num_steps=traffic["num_steps"], axes={
            **traffic["axes"], "n_clients": cfg["n_clients"],
            "taus_profile": list(cfg["taus"])})
        self.exec = ExecutionConfig(eval_fn=bound["eval_fn"],
                                    eval_every=traffic["eval_every"])
        self.to_host_s = []

    def _run(self, seeds):
        import jax
        from jax.profiler import TraceAnnotation

        with TraceAnnotation("study_run"):
            result = self.study.axis("seeds", seeds).run(
                params0=self.params0, config=self.exec, **self.bound["sim"])
        pulled = time.perf_counter()
        with TraceAnnotation("to_host"):
            cells = {name: jax.tree_util.tree_map(np.asarray, result[name])
                     for name in result}
        self.to_host_s.append(time.perf_counter() - pulled)
        return {name: (result.labels(name)["scheduler"], cell)
                for name, cell in cells.items()}

    def compiles(self) -> int:
        from repro.experiments import engine, placement

        return (engine._run_group._cache_size()
                + placement._run_group_sharded._cache_size())

    def setup(self):
        self._run(traffic_mod.run_seeds(self.traffic, self.rng))

    def window(self, seconds: float) -> dict:
        import jax
        from jax.profiler import TraceAnnotation

        self.to_host_s = []
        runs, ends = [], []
        with TraceAnnotation("window"):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                seeds = traffic_mod.run_seeds(self.traffic, self.rng)
                runs.append((seeds, self._run(seeds)))
                ends.append(time.perf_counter() - t0)
            elapsed = time.perf_counter() - t0
        print(f"bench: study runs ended at {ends} s; of each run, "
              f"{self.to_host_s} s pulling results to the host",
              file=sys.stderr)
        answers, client_rounds, cell_rounds, evals = [], 0, 0, 0
        for seeds, cells in runs:
            for sched, cell in cells.values():
                part = cell.history.participation  # (R, T, n)
                client_rounds += int(part.sum())
                cell_rounds += part.shape[0] * part.shape[1]
                evals += int(np.size(cell.evals))
                for r, seed in enumerate(seeds):
                    answers.append({
                        "scheduler": sched, "n_clients": part.shape[2],
                        "seed": seed, "participation": part[r],
                        "weight_sum": cell.history.weight_sum[r],
                        "loss": cell.history.loss[r],
                        "params": jax.tree_util.tree_map(
                            lambda a, r=r: a[r], cell.params)})
        return {"elapsed_s": elapsed, "attempted": len(runs), "failed": 0,
                "answers": answers, "scenario_rounds": cell_rounds,
                "client_rounds": client_rounds, "loss_evals": cell_rounds,
                "acc_evals": evals, "updates": cell_rounds}


DRIVERS = {d.kind: d for d in (StudyRuns,)}
