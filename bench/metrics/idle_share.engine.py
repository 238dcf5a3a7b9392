"""Share of the traced window in which no operation ran on the device
while the host was inside the program's ``study.run`` but not waiting
for results (``engine.wait``): the chip held idle by the program's own
host work, such as grouping, dispatch, compiles and slicing out cells
(averaged over the chips, like ``idle_share.train``)."""

from harness import trace
from metrics import _spans


def read(run):
    runs = _spans.on_trace_clock(run)
    if runs is None:
        return None
    host = []
    for spans in runs:
        (root,) = [(s, e) for name, s, e in spans if name == _spans.ROOT]
        host += _spans.minus(root, [(s, e) for name, s, e in spans
                                    if name == "engine.wait"])
    devs = sorted(run.record["devices"])
    idle = sum(_spans.overlap_ns(trace.idle_gaps(run.record, dev), host)
               for dev in devs) / len(devs)
    lo, hi = trace.window(run.record)
    return 100 * idle / (hi - lo)
