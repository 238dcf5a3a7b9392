"""Benchmark harness — one module per paper table/figure.

  fig1           paper Figure 1 grid (schedulers x arrivals x seeds)
  theory         Theorem 1 bound vs empirical (+ error-floor sweep)
  kernels_bench  kernel-adjacent micro-benchmarks
  roofline_table dry-run roofline terms per (arch x shape x mesh)
  serve_bench    Study service: batched throughput, request latency,
                 executable-cache hit rate, single-trace collapse
  multihost      simulated 2-process jax.distributed grid: per-step
                 collective cost, 1-host-vs-2-process overhead, bitwise
                 gather check

Prints ``name,us_per_call,derived`` CSV. Select with ``--only``. With
``--json PATH`` the rows are additionally written as structured JSON
(suite, name, us_per_call, parsed derived fields) so perf-trajectory
``BENCH_*.json`` files can accumulate across PRs. ``--bench-out [DIR]``
is the one-flag version of the ROADMAP's one-bench-file-per-PR rule: it
writes ``BENCH_<today>.json`` (same named-series schema as
``BENCH_2026-07-27.json``) into DIR (default: the repo root).

    PYTHONPATH=src python -m benchmarks.run [--only fig1,theory] [--fast] \
        [--json BENCH_out.json] [--bench-out]
"""

from __future__ import annotations

import argparse
import datetime
import json
import os
import sys
import traceback

#: Pinned BENCH_*.json series schema (regression-tested in
#: ``tests/test_bench_schema.py``): every series record carries at least
#: ``name`` (the series id compared across PRs), ``values`` (a flat dict
#: of every numeric/bool/str measurement, ``us_per_call`` included) and
#: ``units`` (unit per measured key; derived dimensionless fields are
#: omitted). ``suite`` / ``us_per_call`` / ``derived`` remain for
#: continuity with pre-schema BENCH files.
SCHEMA = "bench-series/v1"


def _parse_derived(derived: str) -> dict:
    """``k1=v1;k2=v2`` -> dict with numeric/bool values where possible."""
    out: dict = {}
    for part in derived.split(";"):
        if "=" not in part:
            if part:
                out.setdefault("notes", []).append(part)
            continue
        k, v = part.split("=", 1)
        if v in ("True", "False"):
            out[k] = v == "True"
        else:
            try:
                out[k] = float(v)
            except ValueError:
                out[k] = v
    return out


def _parse_row(suite: str, row: str) -> dict:
    name, us, derived = row.split(",", 2)
    try:
        us_val = float(us)
    except ValueError:
        us_val = None
    values = _parse_derived(derived)
    values.pop("notes", None)
    values["us_per_call"] = us_val
    return {"suite": suite, "name": name, "us_per_call": us_val,
            "derived": _parse_derived(derived),
            "values": values, "units": {"us_per_call": "us"}}


def check_distinct_timings(records, threshold: int = 3) -> None:
    """Reject mass-duplicated timings across distinct series names.

    Regression guard for the fig1 attribution bug, where every
    ``fig1_<scenario>`` row reported the identical grid-total
    microseconds — 12 series names, one number. A duplicated timing is
    legitimate only when the row *declares* its source via a
    ``timing_ref=<origin series>`` derived field (speedup/summary rows
    quote the measurement they annotate). Within one suite, ``threshold``
    or more distinct names sharing one non-zero ``us_per_call`` without
    such an attribution is an error. Zero/None values are exempt —
    derived series (crossovers, dry-run tables) use 0 as "not a timing".
    """
    groups: dict = {}
    for r in records:
        us = r.get("us_per_call")
        if not us:
            continue
        if "timing_ref" in (r.get("derived") or {}):
            continue
        groups.setdefault((r.get("suite"), us), set()).add(r.get("name"))
    bad = {k: sorted(names) for k, names in groups.items()
           if len(names) >= threshold}
    if bad:
        lines = [f"  suite={suite!r} us_per_call={us}: {names}"
                 for (suite, us), names in sorted(bad.items())]
        raise ValueError(
            "duplicated timing attributed to multiple series (add a "
            "timing_ref derived field or time each series honestly):\n"
            + "\n".join(lines))


def check_serve_series(records) -> None:
    """Validate the ``serve_*`` series family (suite ``serve_bench``).

    A serve series that silently drops its derived counters would turn
    the serving perf trajectory into bare wall times, so the schema is
    enforced here: ``serve_latency`` must carry an ordered p50/p99 pair,
    ``serve_cache`` a hit rate in [0, 1] with non-growing warm compiles,
    ``serve_collapse`` a positive compile count,
    ``serve_resume_latency`` a zero-recompile warm resume, and
    ``serve_resume_bitwise`` must actually be bitwise. Errors name the
    offending series.
    """
    want = {
        "serve_latency": ("p50_us", "p99_us"),
        "serve_cache": ("hit_rate",),
        "serve_collapse": ("compiles",),
        "serve_resume_uninterrupted": ("chunks",),
        "serve_resume_latency": ("resume_us", "overhead_pct",
                                 "new_compiles"),
        "serve_resume_bitwise": ("bitwise",),
    }
    by_name = {r.get("name"): r for r in records
               if r.get("suite") == "serve_bench"}
    if not by_name:
        return
    problems = []
    for name in by_name:
        if not str(name).startswith("serve_"):
            problems.append(
                f"series {name!r}: serve_bench series must be named "
                f"serve_*")
    for name, keys in want.items():
        rec = by_name.get(name)
        if rec is None:
            problems.append(f"series {name!r} missing from serve_bench run")
            continue
        derived = rec.get("derived") or {}
        missing = [k for k in keys if k not in derived]
        if missing:
            problems.append(
                f"series {name!r}: missing derived field(s) {missing}")
            continue
        if name == "serve_latency" and derived["p50_us"] > derived["p99_us"]:
            problems.append(
                f"series {name!r}: p50_us={derived['p50_us']} > "
                f"p99_us={derived['p99_us']}")
        if name == "serve_cache" and not 0 <= derived["hit_rate"] <= 1:
            problems.append(
                f"series {name!r}: hit_rate={derived['hit_rate']} outside "
                f"[0, 1]")
        if name == "serve_cache" and derived.get("warm_compiles", 0) > 0:
            problems.append(
                f"series {name!r}: warm_compiles="
                f"{derived['warm_compiles']} — repeat traffic recompiled")
        if name == "serve_collapse" and not derived["compiles"] >= 1:
            problems.append(
                f"series {name!r}: compiles={derived['compiles']} < 1")
        if name == "serve_resume_latency" \
                and derived["new_compiles"] != 0:
            problems.append(
                f"series {name!r}: new_compiles="
                f"{derived['new_compiles']} — a warm resume recompiled")
        if name == "serve_resume_bitwise" and not derived["bitwise"]:
            problems.append(
                f"series {name!r}: bitwise={derived['bitwise']} — resumed "
                f"responses drifted from the uninterrupted dispatch")
    if problems:
        raise ValueError("invalid serve_* series:\n  " +
                         "\n  ".join(problems))


def check_multihost_series(records) -> None:
    """Validate the ``multihost_*`` series family (suite ``multihost``).

    The acceptance contract of the multi-process path is encoded here:
    the 2-process gather run must stay the bitwise oracle
    (``multihost_bitwise``), both 2-process rows must actually span two
    processes and quote their single-host overhead, and the per-step
    collective-cost row must carry both reduction modes. Errors name the
    offending series.
    """
    by_name = {r.get("name"): r for r in records
               if r.get("suite") == "multihost"}
    if not by_name:
        return
    problems = []
    for name in by_name:
        if not str(name).startswith("multihost_"):
            problems.append(
                f"series {name!r}: multihost series must be named "
                f"multihost_*")
    want = {
        "multihost_baseline_1proc": ("processes", "devices"),
        "multihost_2proc_psum": ("processes", "overhead_pct", "us_per_step"),
        "multihost_2proc_gather": ("processes", "overhead_pct",
                                   "us_per_step"),
        "multihost_step_collective": ("psum_us_per_step",
                                      "gather_us_per_step"),
        "multihost_bitwise": ("bitwise",),
    }
    for name, keys in want.items():
        rec = by_name.get(name)
        if rec is None:
            problems.append(f"series {name!r} missing from multihost run")
            continue
        derived = rec.get("derived") or {}
        missing = [k for k in keys if k not in derived]
        if missing:
            problems.append(
                f"series {name!r}: missing derived field(s) {missing}")
            continue
        if name.startswith("multihost_2proc") and derived["processes"] != 2:
            problems.append(
                f"series {name!r}: processes={derived['processes']} — the "
                f"simulated run did not span two processes")
        if name == "multihost_bitwise" and not derived["bitwise"]:
            problems.append(
                f"series {name!r}: bitwise={derived['bitwise']} — the "
                f"2-process gather run drifted from the single-process "
                f"vmap engine")
    if problems:
        raise ValueError("invalid multihost_* series:\n  " +
                         "\n  ".join(problems))


def build_doc(selected, fast: bool, device_count: int, records, failed, *,
              host_devices: dict | None = None) -> dict:
    """The BENCH_*.json document — one pinned shape for every PR's
    perf-trajectory file.

    ``device_count`` is the *effective* ``jax.device_count()`` at write
    time — if ``ensure_host_device_count`` came too late (jax already
    imported) the series silently ran on whatever the backend had, and
    ``host_devices`` records that: ``requested`` (the placeholder count
    asked for, None if never requested) and ``applied`` (whether the
    flag actually took effect), so BENCH files taken under a failed pin
    are never silently compared against properly-sharded ones.
    """
    return {"schema": SCHEMA, "suites": list(selected), "fast": fast,
            "device_count": device_count,
            "host_devices": host_devices or {"requested": None,
                                             "applied": None},
            "failed": list(failed), "results": list(records)}


def bench_out_path(directory: str, date: str) -> str:
    """One BENCH file per PR: never clobber an earlier PR's series
    landed on the same date — uniquify with a numeric suffix that keeps
    counting past ``.2`` (``BENCH_d.json``, ``BENCH_d.2.json``,
    ``BENCH_d.3.json``, …)."""
    path = os.path.join(directory, f"BENCH_{date}.json")
    suffix = 2
    while os.path.exists(path):
        path = os.path.join(directory, f"BENCH_{date}.{suffix}.json")
        suffix += 1
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--fast", action="store_true",
                    help="shrink grid sizes (iterations/seeds) for CI-speed runs")
    ap.add_argument("--json", default="",
                    help="also write structured results to this JSON path")
    ap.add_argument("--bench-out", nargs="?", const=".", default="",
                    metavar="DIR",
                    help="write BENCH_<date>.json (the per-PR perf-trajectory "
                         "series) into DIR (default: current directory, i.e. "
                         "the repo root when run as documented)")
    args = ap.parse_args()

    suite_names = ("fig1", "theory", "kernels_bench", "roofline_table",
                   "serve_bench", "multihost")
    selected = [s.strip() for s in args.only.split(",") if s.strip()] \
        or list(suite_names)
    unknown = [s for s in selected if s not in suite_names]
    if unknown:
        raise SystemExit(f"unknown suites {unknown}; have {list(suite_names)}")

    host_devices = {"requested": None, "applied": None}
    if "fig1" in selected or "multihost" in selected:
        # 8 placeholder CPU devices so fig1's sharded grid series and
        # the multihost suite's single-process baseline run. Must happen
        # before the suite imports pull in jax, and only when those
        # suites are requested. Whether the pin actually took effect is
        # recorded in the JSON (with the effective device count) so
        # BENCH_* series taken under different backends are never
        # silently compared.
        from repro._env import ensure_host_device_count
        host_devices = {"requested": 8,
                        "applied": ensure_host_device_count(8)}
    sys.path.insert(0, ".")  # examples/ imports
    from benchmarks import (fig1, kernels_bench, multihost, roofline_table,
                            serve_bench, theory)
    from repro._env import enable_compilation_cache

    enable_compilation_cache()

    fig1_kw = (dict(iters=40, seeds=8, n_clients=8) if args.fast
               else dict(iters=100, seeds=8, n_clients=8))
    suites = {
        "fig1": lambda: fig1.run(**fig1_kw),
        "theory": theory.run,
        "kernels_bench": kernels_bench.run,
        "roofline_table": roofline_table.run,
        "serve_bench": lambda: serve_bench.run(fast=args.fast),
        "multihost": lambda: multihost.run(fast=args.fast),
    }
    assert set(suites) == set(suite_names)  # one source of suite names

    print("name,us_per_call,derived")
    records, failed = [], []
    for name in selected:
        try:
            for row in suites[name]():
                print(row, flush=True)
                records.append(_parse_row(name, row))
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failed.append(name)

    try:
        check_distinct_timings(records)
    except ValueError:
        traceback.print_exc()
        failed.append("timing-attribution")

    try:
        check_serve_series(records)
    except ValueError:
        traceback.print_exc()
        failed.append("serve-series")

    try:
        check_multihost_series(records)
    except ValueError:
        traceback.print_exc()
        failed.append("multihost-series")

    out_paths = [p for p in (args.json,) if p]
    if args.bench_out:
        out_paths.append(
            bench_out_path(args.bench_out, datetime.date.today().isoformat()))
    if out_paths:
        import jax

        doc = build_doc(selected, args.fast, jax.device_count(), records,
                        failed, host_devices=host_devices)
        for path in out_paths:
            with open(path, "w") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
            print(f"wrote {path}", file=sys.stderr)

    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
