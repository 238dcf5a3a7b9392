"""The general traffic generator: what a traffic file's parameters turn
into for one seed.

A ``study_runs`` file gives the study's axes, its rounds and the seeds
of a run; every run draws fresh study seeds, so runs differ only in
their seeds.
"""

from __future__ import annotations

SEED_LIMIT = 2**31


def run_seeds(traffic: dict, rng) -> list[int]:
    """The study seeds of one ``study_runs`` run."""
    return [int(s) for s in rng.integers(0, SEED_LIMIT,
                                         traffic["seeds_per_run"])]
