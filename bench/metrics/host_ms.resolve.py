"""Host milliseconds a study run of the window spent grouping scenarios
by structure and stacking their components (``engine.resolve``). The
chip is idle for it."""

from metrics import _spans


def read(run):
    return _spans.per_run_ms(run, "engine.resolve")
