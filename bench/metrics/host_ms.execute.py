"""Host milliseconds a study run of the window spent in the engine
outside its resolve, dispatch and collect spans (``engine.execute``
less those): mostly building the seeds' keys (``_seed_keys``, eager
ops). The chip is idle for it."""

from metrics import _spans


def read(run):
    return _spans.per_run_ms(run, "engine.execute", less=(
        "engine.resolve", "engine.dispatch", "engine.collect"))
