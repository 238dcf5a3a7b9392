"""Device trace: capture with the JAX profiler, and reduce to numbers.

A captured trace is first read into a plain record (:func:`read`)::

    {"devices": {"0": [[name, start_ns, dur_ns, text], ...], ...},
     "host": [[name, start_ns, dur_ns], ...]}

``devices`` holds the operations that ran on each chip (the profiler's
"XLA Ops" line of each ``/device:TPU:<id>`` plane): ``name`` is the HLO
instruction's name without its number (``%reduce-window``), ``text`` its
whole HLO line and string stats. Control-flow ops (``while``,
``conditional``, ``call``) span the ops of their bodies and are left
out. ``host`` holds the benchmark's own spans (:data:`SPANS`).
Everything after that works on the record, so the reduction is tested on
a small recorded one.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from contextlib import contextmanager

#: Host spans the benchmark writes around its calls into the program.
SPANS = ("window", "study_run", "to_host")

#: Trace line that holds one event per device operation.
OPS_LINE = "XLA Ops"
#: HLO ops whose events span the ops of their bodies.
CONTAINER = re.compile(r" (while|conditional|call)\(")


@contextmanager
def capture():
    """Trace the body; yields a dict that holds the reduced record
    under ``"record"`` once the body has ended. The raw trace lives in a
    temporary directory and is deleted after reading."""
    import jax

    out: dict = {}
    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        jax.profiler.start_trace(tmp)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        out["record"] = read(path)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def read(path: str) -> dict:
    """The plain record of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            dev = plane.name.rsplit(":", 1)[1]
            ops = devices.setdefault(dev, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    if CONTAINER.search(e.name):
                        continue
                    text = " ".join([e.name] + [str(v) for _, v in e.stats
                                                if isinstance(v, str)])
                    name = re.sub(r"\.\d+$", "", e.name.split(" = ")[0])
                    ops.append([name, int(e.start_ns), int(e.duration_ns),
                                text])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPANS:
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"devices": devices, "host": host}


def window(record) -> tuple[int, int]:
    """(start_ns, end_ns) of the benchmark's ``window`` span."""
    (start, dur), = [(s, d) for n, s, d in record["host"] if n == "window"]
    return start, start + dur


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def busy(record, dev: str) -> list[tuple[int, int]]:
    """Union of the intervals in which an operation ran on ``dev``,
    inside the window."""
    lo, hi = window(record)
    return clip(union((s, s + d) for _, s, d, _ in record["devices"][dev]),
                lo, hi)


def busy_s(record) -> float:
    """Busy seconds in the window, averaged over the chips."""
    devs = sorted(record["devices"])
    return sum(sum(e - s for s, e in busy(record, d))
               for d in devs) / len(devs) / 1e9


def window_s(record) -> float:
    lo, hi = window(record)
    return (hi - lo) / 1e9


def idle_gaps(record, dev: str) -> list[tuple[int, int]]:
    lo, hi = window(record)
    gaps, at = [], lo
    for s, e in busy(record, dev):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def host_doing(record, start: int, end: int) -> str:
    """The innermost benchmark span (other than the window) that covers
    most of ``[start, end)``, or ``"no span"``."""
    best, best_cover, best_len = "no span", 0, None
    for name, s, d in record["host"]:
        if name == "window":
            continue
        cover = min(end, s + d) - max(start, s)
        if cover > best_cover or (cover == best_cover and cover > 0
                                  and d < best_len):
            best, best_cover, best_len = name, cover, d
    return best if best_cover * 2 >= end - start else "no span"


def breakdown(record, top: int = 10) -> dict:
    """The device ops that took most time (summed by name, over all
    chips) and the longest idle gaps of chip 0 by what the host was
    doing, each as ``[name, seconds]``."""
    lo, hi = window(record)
    per_op: dict[str, int] = {}
    for ops in record["devices"].values():
        for name, s, d, _ in ops:
            if s + d > lo and s < hi:
                per_op[name] = per_op.get(name, 0) + d
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    dev = sorted(record["devices"])[0]
    gaps = sorted(idle_gaps(record, dev), key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[n, d / 1e9] for n, d in ops],
            "idle_gaps": [[host_doing(record, s, e), (e - s) / 1e9]
                          for s, e in gaps]}


def select(record, dev: str, needle: str) -> list[tuple[int, int]]:
    """(start, end) of ``dev``'s ops in the window whose name or stats
    contain ``needle``."""
    lo, hi = window(record)
    return clip(((s, s + d) for name, s, d, text in record["devices"][dev]
                 if needle in name or needle in text), lo, hi)
