"""Share of the traced window of study runs in which no operation ran on
the device (averaged over the chips)."""

from harness import trace


def read(run):
    if run.record is None or run.traffic["kind"] != "study_runs":
        return None
    return 100 * (1 - trace.busy_s(run.record) / trace.window_s(run.record))
