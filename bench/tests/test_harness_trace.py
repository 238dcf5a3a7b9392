"""The trace-to-metrics reduction on a small recorded trace."""

import pytest
from bench_tiny import BENCH  # noqa: F401  (puts bench/ on the path)
from harness import trace

# Chip 0: ops at [100, 200), [150, 300) (overlapping), the update kernel
# at [400, 450), an all-reduce at [600, 700) of which [600, 650) overlaps
# a fusion; chip 1 busy [100, 900). The window is [100, 1100); the host
# ran a study between 100 and 500 and pulled results between 500 and
# 1000.
RECORD = {
    "devices": {
        "0": [["fusion.1", 100, 100, "convolution"],
              ["fusion.2", 150, 150, "convolution"],
              ["%masked_scaled_aggregate_update_kernel", 400, 50,
               "tpu_custom_call"],
              ["all-reduce.3", 600, 100, "all-reduce"],
              ["fusion.4", 590, 60, "add"],
              ["fusion.9", 2000, 50, "after the window"]],
        "1": [["fusion.1", 100, 800, "convolution"]],
    },
    "host": [["window", 100, 1000], ["study_run", 100, 400],
             ["to_host", 500, 500]],
}


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3),
                                                             (5, 8)]


def test_busy_is_the_union_inside_the_window():
    assert trace.busy(RECORD, "0") == [(100, 300), (400, 450), (590, 700)]
    # chip 0: 200 + 50 + 110 = 360 ns; chip 1: 800 ns; mean 580 ns
    assert trace.busy_s(RECORD) == pytest.approx(580e-9)
    assert trace.window_s(RECORD) == pytest.approx(1000e-9)


def test_idle_gaps_and_what_the_host_did():
    assert trace.idle_gaps(RECORD, "0") == [(300, 400), (450, 590),
                                            (700, 1100)]
    bd = trace.breakdown(RECORD)
    assert bd["idle_gaps"][0] == ["to_host", pytest.approx(400e-9)]
    assert ["study_run", pytest.approx(100e-9)] in bd["idle_gaps"]
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(900e-9)]
    assert all(name != "fusion.9" for name, _ in bd["device_ops"])


def test_kernel_events_are_selected_by_name():
    kernel = "masked_scaled_aggregate_update"
    assert trace.select(RECORD, "0", kernel) == [(400, 450)]
    assert trace.select(RECORD, "1", kernel) == []


def test_idle_share_reader():
    from harness.main import Run, load_module

    reader = load_module(BENCH / "metrics" / "idle_share.train.py", "idle")
    run = Run(record=RECORD, traffic={"kind": "study_runs"})
    assert reader.read(run) == pytest.approx(100 * (1 - 0.58))
    run = Run(record=RECORD, traffic={"kind": "open_loop"})
    assert reader.read(run) is None
