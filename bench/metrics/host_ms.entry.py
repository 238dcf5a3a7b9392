"""Host milliseconds a study run of the window spent in ``Study.run``
outside the engine (``study.run`` less ``engine.execute``): building the
scenarios, the seeds' keys and the result. The chip is idle for it."""

from metrics import _spans


def read(run):
    return _spans.per_run_ms(run, "study.run", less=("engine.execute",))
