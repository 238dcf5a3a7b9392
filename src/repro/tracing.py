"""Host spans and counters of the program, and the profiler's view of them.

``span(name)`` times a block of host code twice over: it enters
``jax.profiler.TraceAnnotation(name)``, so a profiler trace shows the
block on the device trace's clock, and it records ``(name, parent,
start_ns, end_ns)`` (``time.perf_counter_ns``) in the open record of the
current thread. A span opened while none is open is a root: its
:class:`Record` holds every span and counter opened under it, and is
kept once the root closes, the newest :data:`KEEP` of them
(:func:`runs`). ``span`` also decorates a function. Device code is
named with ``jax.named_scope`` instead: the names become part of each
compiled op's ``op_name`` metadata.

``count(name, n)`` adds to the open root's counters. JAX's own compile
events are counted here too: ``compiles`` for every backend compile and
``cache_loads`` for every program loaded from the persistent
compilation cache instead, each noted with the function's name and
seconds.

The open record lives in a ``contextvars`` variable, so each thread (the
service's concurrent flushers) builds its own. Always on: a study run
opens about 15 spans.
"""

from __future__ import annotations

import collections
import contextvars
import dataclasses
import time
from contextlib import contextmanager
from typing import NamedTuple

import jax

#: Records kept, oldest dropped first.
KEEP = 64
#: JAX's time span of one backend compile, or of a persistent-cache load.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: JAX's event for a program loaded from the persistent cache.
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Span(NamedTuple):
    name: str
    parent: str | None
    start_ns: int
    end_ns: int


@dataclasses.dataclass
class Record:
    """One root span's record: its spans in the order they closed (the
    root last), its counters, and the attributes given with counts."""

    name: str
    spans: list[Span] = dataclasses.field(default_factory=list)
    counters: dict[str, int] = dataclasses.field(default_factory=dict)
    notes: list[dict] = dataclasses.field(default_factory=list)

    @property
    def root(self) -> Span:
        return self.spans[-1]


_RUNS: collections.deque[Record] = collections.deque(maxlen=KEEP)
#: Open spans of this context, outermost first: (record, name) pairs.
_OPEN: contextvars.ContextVar[tuple] = contextvars.ContextVar(
    "repro_tracing_open", default=())
#: A persistent-cache hit seen inside the open compile span.
_HIT: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "repro_tracing_hit", default=False)


@contextmanager
def span(name: str):
    """Time the block as ``name``, under the innermost open span."""
    stack = _OPEN.get()
    record = stack[0][0] if stack else Record(name)
    parent = stack[-1][1] if stack else None
    with jax.profiler.TraceAnnotation(name):
        token = _OPEN.set(stack + ((record, name),))
        start = time.perf_counter_ns()
        try:
            yield record
        finally:
            end = time.perf_counter_ns()
            _OPEN.reset(token)
            record.spans.append(Span(name, parent, start, end))
            if not stack:
                _RUNS.append(record)


def count(name: str, n: int = 1, **attrs) -> None:
    """Add ``n`` to the open root's counter ``name``; ``attrs`` are noted
    beside it. Without an open span nothing is kept."""
    stack = _OPEN.get()
    if not stack:
        return
    record = stack[0][0]
    record.counters[name] = record.counters.get(name, 0) + n
    if attrs:
        record.notes.append({"counter": name, "n": n, **attrs})


def runs() -> list[Record]:
    """The kept records, oldest first."""
    return list(_RUNS)


def _on_event(event: str, **kw) -> None:
    if event == CACHE_HIT_EVENT:
        _HIT.set(True)


def _on_time_span(event: str, start: float, end: float, **kw) -> None:
    # JAX times a persistent-cache load as a backend compile too; the hit
    # event inside the span tells the two apart.
    if event != COMPILE_EVENT:
        return
    loaded = _HIT.get()
    _HIT.set(False)
    count("cache_loads" if loaded else "compiles",
          fun_name=kw.get("fun_name", "?"), seconds=end - start)


jax.monitoring.register_event_listener(_on_event)
jax.monitoring.register_event_time_span_listener(_on_time_span)
