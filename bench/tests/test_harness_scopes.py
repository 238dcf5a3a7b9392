"""The readers of the program's host spans and counters, on a small
recorded trace and fake program records."""

import sys

import pytest
from bench_tiny import BENCH  # noqa: F401  (puts bench/ on the path)
from harness.main import Run, load_module

from repro import tracing
from repro.tracing import Record, Span


def op(name, start, dur):
    """An op as the v5e's trace records it: the event's name is the HLO
    instruction's line, and it carries no string stats."""
    return [f"%{name}", start, dur, f"%{name}.1 = f32[8] {name}()"]


# One chip; the window is [0, 10000). Two study runs: [50, 4000) and
# [4500, 9000). The first keeps the chip busy [100, 2000), idles
# [2000, 2600) while the host slices out cells (the planted gap), and
# is busy again [2600, 3900); the second is busy [4550, 8950).
RECORD = {
    "devices": {"0": [
        op("fusion", 100, 1000),
        op("reduce-window", 1100, 800),
        op("copy", 1900, 100),
        op("fusion", 2600, 1300),
        op("fusion", 4550, 4400),
        op("fusion", 12000, 50),  # after the window
    ]},
    "host": [["window", 0, 10000], ["study_run", 50, 3950],
             ["to_host", 4000, 500], ["study_run", 4500, 4500],
             ["to_host", 9000, 1000]],
}


def record(t0, spans, **counters):
    """A ``study.run`` record whose root opens at ``t0`` on the host's
    own clock; ``spans`` are (name, start, end) relative to the root."""
    rec = Record("study.run", counters=dict(counters))
    for name, s, e in spans:
        rec.spans.append(Span(name, "engine.execute", t0 + s, t0 + e))
    end = max(e for _, _, e in spans)
    rec.spans.append(Span("study.run", None, t0, t0 + end))
    return rec


# Host clocks far from the trace's, each run at its own offset. The first
# run waits [100, 1900) after its start, i.e. [150, 1950) on the trace;
# the second [100, 4400), i.e. [4600, 8900).
PROGRAM = [
    record(7_000_000, [("engine.execute", 0, 0), ("engine.resolve", 0, 5)],
           compiles=3),  # set-up's run, before the window
    record(5_000_000, [("engine.resolve", 10, 50),
                       ("engine.dispatch", 50, 100),
                       ("engine.wait", 100, 1900),
                       ("engine.collect", 100, 2700),
                       ("engine.execute", 5, 3950)]),
    record(9_000_000, [("engine.dispatch", 20, 100),
                       ("engine.wait", 100, 4400),
                       ("engine.collect", 100, 4500),
                       ("engine.execute", 20, 4500)], cache_loads=1),
]
PROGRAM[2].notes.append({"counter": "cache_loads", "n": 1,
                         "fun_name": "run_group", "seconds": 0.25})
HOST_MS = ["host_ms.entry", "host_ms.execute", "host_ms.resolve",
           "host_ms.dispatch", "host_ms.collect"]


def reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py",
                       f"bench_metric_{name}")


@pytest.fixture
def run(monkeypatch):
    monkeypatch.setattr(tracing, "runs", lambda: list(PROGRAM))
    return Run(record=RECORD, facts={"attempted": 2},
               traffic={"kind": "study_runs"})


def test_idle_share_engine_attributes_the_planted_gap(run):
    # Idle while in study.run but not waiting: run 1 [50, 100) before
    # its first op, the planted [2000, 2600), [3900, 4000) after its
    # last; run 2 [4500, 4550) and [8950, 9000). The gaps while waiting
    # and outside the study runs are not the engine's.
    share = reader("idle_share.engine").read(run)
    assert share == pytest.approx(100 * (50 + 600 + 100 + 50 + 50) / 10000)
    # with the planted gap filled, the reading drops by its 6%
    filled = {**RECORD, "devices": {"0": RECORD["devices"]["0"]
                                    + [op("fusion", 2000, 600)]}}
    run = Run(record=filled, facts=run.facts, traffic=run.traffic)
    assert reader("idle_share.engine").read(run) == pytest.approx(share - 6)


def test_window_compiles_count_only_the_window_runs(run, capsys):
    assert reader("window_compiles").read(run) == 1
    # what was loaded, and how long it took, is named on stderr
    assert "cache_loads run_group 0.250 s" in capsys.readouterr().err


@pytest.mark.parametrize("name, ns", [
    ("host_ms.entry", (5 + 20) / 2),  # study.run outside engine.execute
    # engine.execute less its resolve, dispatch and collect
    ("host_ms.execute", ((3945 - 40 - 50 - 2600) + (4480 - 80 - 4400)) / 2),
    ("host_ms.resolve", 40 / 2),  # the second run resolved nothing
    ("host_ms.dispatch", (50 + 80) / 2),
    ("host_ms.collect", (800 + 100) / 2),  # engine.collect less its wait
])
def test_host_ms_reads_the_window_runs_spans(run, name, ns):
    assert reader(name).read(run) == pytest.approx(ns / 1e6)


@pytest.mark.parametrize("facts", [{"attempted": 4}, {"attempted": 1},
                                   {"attempted": 0}],
                         ids=["more-runs-than-records",
                              "fewer-runs-than-spans", "no-runs"])
def test_span_readers_are_none_when_the_counts_disagree(run, facts):
    run = Run(record=RECORD, facts=facts, traffic=run.traffic)
    assert reader("idle_share.engine").read(run) is None
    if facts["attempted"] != 1:  # one record is there for one run
        for name in ["window_compiles"] + HOST_MS:
            assert reader(name).read(run) is None, name


def test_span_readers_are_none_without_the_program_records(run,
                                                            monkeypatch):
    import repro

    # as on a program that has no repro.tracing
    monkeypatch.delattr(repro, "tracing")
    monkeypatch.setitem(sys.modules, "repro.tracing", None)
    for name in ["idle_share.engine", "window_compiles"] + HOST_MS:
        assert reader(name).read(run) is None, name
