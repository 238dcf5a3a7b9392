"""Host milliseconds a study run of the window spent slicing its cells
out of the groups' outputs and checking them (``engine.collect`` less
``engine.wait``, summed over the groups). The slicing of a group's first
cell is dispatched behind its program, so part of this is hidden."""

from metrics import _spans


def read(run):
    return _spans.per_run_ms(run, "engine.collect", less=("engine.wait",))
