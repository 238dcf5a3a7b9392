"""The configuration's data set, made by the module its ``dataset`` key
names: ``bench/datasets/<dataset>.py``, whose ``make(cfg, seed)`` returns
the client shards ``shards_x`` and ``shards_y`` (leading axes ``(N, D)``:
clients, examples), the held-out loss set ``loss_x``, ``loss_y`` and the
evaluation set ``eval_x``, ``eval_y``. Trailing axes and dtypes are the
data set's own."""

from __future__ import annotations

from pathlib import Path

DATASETS = Path(__file__).resolve().parents[1] / "datasets"


def make(cfg: dict, seed: int) -> dict:
    """The data set ``cfg["dataset"]`` made from ``seed``."""
    from harness.main import load_module

    path = DATASETS / f"{cfg['dataset']}.py"
    if not path.is_file():
        raise ValueError(f"unknown dataset {cfg['dataset']!r}; have "
                         f"{sorted(p.stem for p in DATASETS.glob('*.py'))}")
    return load_module(path, f"bench_dataset_{cfg['dataset']}").make(cfg,
                                                                     seed)
