"""The fused reduce-and-update kernel's share of its roofline: the HBM
bytes it has to move (every client row, the parameters in and out) at
the chip's HBM bandwidth, over the device time of its events in the
trace. Memory-bound, so the bytes set the bound."""

from harness import trace
from metrics import _counts

#: The fused update kernel's name in the trace (its Pallas wrapper's).
KERNEL = "masked_scaled_aggregate_update"


def read(run):
    if "updates" not in run.facts or run.record is None:
        return None
    secs = sum(e - s for dev in run.record["devices"]
               for s, e in trace.select(run.record, dev, KERNEL)) / 1e9
    if secs <= 0:
        return None
    cfg = run.cfg
    bytes_ = run.facts["updates"] * _counts.update_bytes(
        cfg["model"]["n_params"], cfg["n_clients"])
    return 100 * bytes_ / run.peaks["hbm_bytes_per_s"] / secs
