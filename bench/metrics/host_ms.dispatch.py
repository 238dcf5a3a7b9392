"""Host milliseconds a study run of the window spent handing its
structure groups' programs to the device (``engine.dispatch``, summed
over the groups); a compile inside the window would land here."""

from metrics import _spans


def read(run):
    return _spans.per_run_ms(run, "engine.dispatch")
