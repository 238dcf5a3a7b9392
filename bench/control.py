#!/usr/bin/env python3
"""Readings of the comparison's control, on the chip at a cell's size.

    python3 bench/control.py --workload fig1_cnn.grid --seeds 11 12 13

The control is the configuration's reference computed in bfloat16 (the
precision below the float32 the configuration states) in the program's
place. For each seed it draws as many answers as a run of the cell
compares, over the cell's schedulers and populations, and prints one JSON
line per seed with the numbers of the control and of two faults put in
the program's place: the reference with half of each client's batch left
out (the mean taken over the rest), and a state left unchanged (the
parameters and the held-out loss stay as they were before the first
round). Benchmark runs
never run this; it gives the upper readings the limits in the
configuration file were set from (PERF.md).
"""

from __future__ import annotations

import argparse
import itertools
import json
import sys
from functools import partial
from pathlib import Path

HERE = Path(__file__).resolve().parent
for _p in (HERE, HERE.parent / "src", HERE.parent):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import check, data as data_mod, device, fedref
    from harness import main as harness

    c = harness.load_cell(args.workload)
    cfg, traffic = c["cfg"], c["traffic"]
    print(json.dumps({"device": device.require_tpu(c["cell"]["chips"])}),
          flush=True)
    schedulers = traffic.get("schedulers") or traffic["axes"]["scheduler"]
    sizes = traffic.get("n_clients") or [cfg["n_clients"]]
    model = harness.config_module(c, "reference")
    data = data_mod.make(cfg, cfg["data"]["seed"])
    init = jax.jit(partial(model.init_params, model=cfg["model"]))
    for seed in args.seeds:
        rng = np.random.default_rng(seed)
        key = jax.random.PRNGKey(int(rng.integers(0, 2**31)))
        ref = check.Reference(cfg, model, data, lambda: init(key))
        kinds = list(itertools.product(schedulers, sizes))
        draws = [(s, n, int(rng.integers(0, 2**31)))
                 for s, n in (kinds[i % len(kinds)] for i in
                              range(traffic["check_answers"]))]
        rounds = traffic["num_steps"]
        out = {"seed": seed, "answers": len(draws)}
        planted = {"control": {"dtype": jnp.bfloat16},
                   "half_batch": {"keep": cfg["batch_size"] // 2}}
        for name in (*planted, "unchanged"):
            out[name] = dict.fromkeys(check.NUMBERS, 0.0)
        dev = ref._device()
        start = float(fedref._held_out_loss(
            dev["params0"], dev["loss_x"], dev["loss_y"], model=ref.model,
            precision=jax.lax.Precision.HIGHEST))
        for s, n, study_seed in draws:
            want = ref.answer(s, n, study_seed, rounds)
            got = {name: ref.answer(s, n, study_seed, rounds, **kw)
                   for name, kw in planted.items()}
            got["unchanged"] = dict(want, loss=np.full_like(want["loss"],
                                                            start),
                                    params=ref.params0)
            for name, answer in got.items():
                for k, v in check.numbers(answer, want, ref.params0).items():
                    out[name][k] = (max(out[name][k], v) if np.isfinite(v)
                                    else float("inf"))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
