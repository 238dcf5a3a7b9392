"""Energy-aware distributed LM training driver.

Runs any ``--arch`` (full or ``--reduced`` smoke variant) under any
scheduler (alg1 / alg2 / benchmark1 / benchmark2 / oracle) and any
registered arrival family (periodic / binary / uniform / the
non-stationary day_night profile). The energy scheduler runs as a tiny jitted state machine beside
the jitted SPMD train step; the (mask, scale) it emits each step is the
paper's eq. (11/12) weighting, applied inside the train step with zero
extra collective traffic.

CPU example (end-to-end, ~100M params):
    PYTHONPATH=src python -m repro.launch.train --arch stablelm-1.6b \
        --reduced --steps 200 --global-batch 16 --seq-len 128 \
        --scheduler alg1 --arrivals periodic
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro._env import enable_compilation_cache
from repro.checkpoint import CheckpointManager, latest_step
from repro.configs import get_config
from repro.core.energy import arrival_family_names
from repro.data import GlobalBatcher, make_lm_tokens
from repro.experiments import build_components
from repro.launch.steps import make_train_step
from repro.models import count_params, init_lm
from repro.optim import adamw


def default_scheduler_for(arrivals: str, requested: str) -> str:
    if requested != "auto":
        return requested
    return "alg1" if arrivals == "periodic" else "alg2"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--n-clients", type=int, default=8)
    ap.add_argument("--scheduler", default="auto",
                    help="auto|alg1|alg2|benchmark1|benchmark2|oracle")
    ap.add_argument("--arrivals", default="periodic",
                    choices=arrival_family_names())
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="",
                    help="legacy params-only checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--checkpoint-dir", default="",
                    help="full-state resumable checkpoints (train state + "
                         "scheduler/energy state + data RNG), written "
                         "atomically every --ckpt-every steps")
    ap.add_argument("--resume", action="store_true",
                    help="continue from the latest checkpoint in "
                         "--checkpoint-dir; the resumed run is bitwise "
                         "identical to the uninterrupted one")
    ap.add_argument("--halt-at", type=int, default=0,
                    help="stop right after the full-state checkpoint at "
                         "this step (simulated preemption; components are "
                         "still built for the full --steps horizon)")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    key = jax.random.PRNGKey(args.seed)
    k_param, k_data, k_sched, k_energy, k_batch = jax.random.split(key, 5)

    params = init_lm(k_param, cfg)
    print(f"arch={cfg.name} reduced={args.reduced} "
          f"params={count_params(params):,}")

    lm = make_lm_tokens(args.seed, 512, args.seq_len, cfg.vocab)
    batcher = GlobalBatcher({"raw": lm.tokens}, n_clients=args.n_clients,
                            global_batch=args.global_batch)

    sched_name = default_scheduler_for(args.arrivals, args.scheduler)
    # Same axis registry the Study API sweeps over — a driver run is the
    # one-cell special case of a study.
    scheduler, energy = build_components(
        scheduler=sched_name, arrivals=args.arrivals,
        n_clients=args.n_clients, horizon=args.steps + 1)

    init_state, train_step = make_train_step(
        cfg, args.n_clients, optimizer=adamw(args.lr))
    state = init_state(params)
    train_step = jax.jit(train_step, donate_argnums=(0,))

    sched_state = scheduler.init(k_sched)
    energy_state = energy.init(k_energy)
    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    full_ckpt = (CheckpointManager(args.checkpoint_dir)
                 if args.checkpoint_dir else None)

    start_step = 0
    if args.resume:
        # The loop state is exactly (train state, scheduler state, energy
        # state, data RNG): restoring all four and re-entering the loop at
        # the saved step replays the identical step stream, so a resumed
        # run is bitwise equal to the uninterrupted one (DESIGN.md §10).
        if full_ckpt is None:
            raise SystemExit("--resume requires --checkpoint-dir")
        last = latest_step(args.checkpoint_dir)
        if last is not None:
            template = {"state": state, "sched_state": sched_state,
                        "energy_state": energy_state, "k_batch": k_batch}
            restored, start_step = full_ckpt.restore(template, last)
            state, sched_state = restored["state"], restored["sched_state"]
            energy_state, k_batch = (restored["energy_state"],
                                     restored["k_batch"])
            print(f"resumed from {full_ckpt.path(start_step)}")

    @jax.jit
    def sched_step(sched, en, sstate, estate, t, k):
        # Scheduler + energy process are pytrees: traced arguments, not
        # closed-over Python objects.
        k1, k2 = jax.random.split(k)
        estate, arr = en.arrivals(estate, t, k1)
        sstate, dec = sched.step(sstate, t, k2, arr)
        return sstate, estate, dec.mask, dec.scale

    t_start = time.time()
    losses = []
    for step in range(start_step, args.steps):
        k_batch, kb, ks = jax.random.split(k_batch, 3)
        batch_raw = batcher.sample(kb)
        batch = {
            "tokens": batch_raw["raw"][:, :-1],
            "labels": batch_raw["raw"][:, 1:],
            "client_ids": batch_raw["client_ids"],
        }
        if cfg.n_vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (args.global_batch, cfg.n_vision_tokens, cfg.d_model),
                cfg.dtype)
        if cfg.enc_dec:
            batch["audio_feats"] = jnp.zeros(
                (args.global_batch, cfg.enc_len, cfg.d_model), cfg.dtype)
        sched_state, energy_state, mask, scale = sched_step(
            scheduler, energy, sched_state, energy_state, jnp.asarray(step), ks)
        state, metrics = train_step(state, batch, mask, scale)
        losses.append(float(metrics["loss"]))
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss={losses[-1]:.4f}  "
                  f"active={float(metrics['active_clients']):.0f}/"
                  f"{args.n_clients}  wsum={float(metrics['weight_sum']):.3f}")
        if ckpt and step and step % args.ckpt_every == 0:
            ckpt.save(step, state.params)
        if full_ckpt and (step + 1) % args.ckpt_every == 0:
            full_ckpt.save(step + 1, {
                "state": state, "sched_state": sched_state,
                "energy_state": energy_state, "k_batch": k_batch})
        if args.halt_at and step + 1 == args.halt_at:
            if full_ckpt is None:
                raise SystemExit("--halt-at requires --checkpoint-dir")
            if (step + 1) % args.ckpt_every != 0:
                full_ckpt.save(step + 1, {
                    "state": state, "sched_state": sched_state,
                    "energy_state": energy_state, "k_batch": k_batch})
            print(f"halted at step {step + 1} (simulated preemption)")
            return losses

    dt = time.time() - t_start
    done = args.steps - start_step
    tail = (f"loss {losses[0]:.4f} -> {np.mean(losses[-10:]):.4f}"
            if losses else "already complete")
    print(f"done: {done} steps in {dt:.1f}s "
          f"({max(done, 1) / dt:.2f} steps/s); {tail}")
    if ckpt:
        ckpt.save(args.steps, state.params)
    if full_ckpt:
        full_ckpt.save(args.steps, {
            "state": state, "sched_state": sched_state,
            "energy_state": energy_state, "k_batch": k_batch})
    return losses


if __name__ == "__main__":
    enable_compilation_cache()
    main()
