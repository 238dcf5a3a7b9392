"""Programs compiled, or loaded from the persistent compilation cache,
inside the window: the ``compiles`` and ``cache_loads`` counters of the
program's records of the window's study runs. Set-up runs every shape,
so this reads 0; where it does not, the functions and their seconds go
to standard error."""

import sys

from metrics import _spans


def read(run):
    records = _spans.window_records(run)
    if records is None:
        return None
    for record in records:
        for note in record.notes:
            print(f"window_compiles: {note['counter']} {note['fun_name']} "
                  f"{note['seconds']:.3f} s", file=sys.stderr)
    return sum(r.counters.get("compiles", 0) + r.counters.get("cache_loads", 0)
               for r in records)
