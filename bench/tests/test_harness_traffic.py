"""Each traffic file rehearsed on the CPU at a tiny size: the generator
is deterministic in ``--seed``, and a seed changes only the study seeds,
never the set of sizes and arrivals."""

import json

import numpy as np
import pytest
from bench_tiny import BENCH, run_tiny, with_traffic
from harness import traffic

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def test_run_seeds_are_deterministic_in_the_seed():
    grid = json.loads((BENCH / "traffic" / "grid.json").read_text())
    a = traffic.run_seeds(grid, np.random.default_rng(2**31 + 5))
    b = traffic.run_seeds(grid, np.random.default_rng(2**31 + 5))
    c = traffic.run_seeds(grid, np.random.default_rng(6))
    assert a == b != c
    assert len(a) == len(c) == grid["seeds_per_run"]
    assert all(0 <= s < traffic.SEED_LIMIT for s in a + c)


@pytest.mark.parametrize("mix", MIXES)
def test_rehearsal_is_deterministic_in_the_seed(monkeypatch, mix):
    """Two whole runs of one seed generate the same traffic (the study
    seeds of the runs both complete), and both are correct."""
    made = []
    real = traffic.run_seeds

    def record(*a, **kw):
        out = real(*a, **kw)
        made[-1].append(out)
        return out

    monkeypatch.setattr(traffic, "run_seeds", record)
    results = []
    for _ in range(2):
        made.append([])
        results.append(run_tiny(monkeypatch, "fig1_cnn.grid", seed=2**31 + 3,
                                edit=with_traffic(mix)))
    both = min(len(m) for m in made)
    assert both and made[0][:both] == made[1][:both]
    for result in results:
        assert result["correct"], result["compared"]
        assert result["attempted"] > 0 and result["failed"] == 0
