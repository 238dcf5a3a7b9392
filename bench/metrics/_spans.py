"""The program's own host records (``repro.tracing``) of the window's
study runs: their spans' host time, and the spans put on the device
trace's clock.

Nothing of the program runs between the end of the window and the
metric readers, so the window's records are the last ``attempted``
records whose root is ``study.run``. Each is shifted onto the trace's
clock by the start of the benchmark's ``study_run`` span around the same
call: the two open microseconds apart."""

from __future__ import annotations

from harness import trace

ROOT = "study.run"


def window_records(run) -> list | None:
    """The program's records of the window's study runs, oldest first;
    None where the program keeps none, or fewer than the window ran."""
    try:
        from repro import tracing
    except ImportError:
        return None
    n = run.facts.get("attempted", 0)
    records = [r for r in tracing.runs() if r.name == ROOT]
    if n <= 0 or len(records) < n:
        return None
    return records[-n:]


def on_trace_clock(run) -> list[list[tuple[str, int, int]]] | None:
    """Each window record's spans as ``(name, start_ns, end_ns)`` on the
    trace's clock; None where there is no trace, or the records and the
    benchmark's ``study_run`` spans in the window differ in number."""
    records = window_records(run)
    if records is None or run.record is None:
        return None
    lo, hi = trace.window(run.record)
    starts = sorted(s for name, s, _ in run.record["host"]
                    if name == "study_run" and lo <= s < hi)
    if len(starts) != len(records):
        return None
    out = []
    for record, start in zip(records, starts):
        shift = start - record.root.start_ns
        out.append([(sp.name, sp.start_ns + shift, sp.end_ns + shift)
                    for sp in record.spans])
    return out


def minus(outer: tuple[int, int], holes) -> list[tuple[int, int]]:
    """``outer`` with the intervals ``holes`` taken out."""
    out, at = [], outer[0]
    for s, e in trace.clip(trace.union(holes), *outer):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if outer[1] > at:
        out.append((at, outer[1]))
    return out


def overlap_ns(a, b) -> int:
    """Nanoseconds in both of two lists of intervals."""
    b = trace.union(b)
    return sum(max(0, min(e1, e2) - max(s1, s2))
               for s1, e1 in trace.union(a) for s2, e2 in b)


def per_run_ms(run, name: str, less: tuple[str, ...] = ()) -> float | None:
    """Milliseconds a study run of the window spent in the program's
    spans ``name`` (summed over a run, averaged over the runs), less the
    time in the spans ``less`` nested inside them; None where the
    records are not there."""
    records = window_records(run)
    if records is None:
        return None
    total = 0
    for record in records:
        for sp in record.spans:
            if sp.name == name:
                total += sp.end_ns - sp.start_ns
            elif sp.name in less:
                total -= sp.end_ns - sp.start_ns
    return total / len(records) / 1e6
