"""Pre-jax-import environment helpers.

This module must stay free of jax (and jax-importing repro) imports at
module level: its callers run *before* the first jax import, which is
the only moment XLA client flags can still take effect
(:func:`enable_compilation_cache` imports jax when it is called).
(``repro`` is a namespace package — no ``__init__.py`` — so
``from repro._env import ...`` pulls in nothing else.)
"""

from __future__ import annotations

import os
import sys
import warnings
from pathlib import Path


def ensure_host_device_count(n: int = 8) -> bool:
    """Merge ``--xla_force_host_platform_device_count=n`` into XLA_FLAGS.

    Gives the CPU backend ``n`` placeholder devices so the sharded grid
    path (DESIGN.md §5) can run on hosts without accelerators. Existing
    ``XLA_FLAGS`` content is preserved; an explicit device-count flag
    from the environment wins; real TPU/GPU backends ignore the flag.

    Returns True if the flag was added, False if it was too late (jax
    already imported — a :class:`UserWarning` names the device count the
    session is actually stuck with) or a device-count flag was already
    present (the environment's explicit choice wins, silently — that is
    the documented contract, not a failure).
    """
    if "jax" in sys.modules:
        warnings.warn(
            "ensure_host_device_count(%d) called after jax was imported — "
            "XLA client flags no longer take effect; this process keeps "
            "jax.device_count()=%s. Sharded suites will silently run on "
            "whatever that is (1 means no sharding at all); call this "
            "before anything imports jax." % (n, _imported_device_count()),
            stacklevel=2)
        return False
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return False
    os.environ["XLA_FLAGS"] = \
        f"{flags} --xla_force_host_platform_device_count={n}".strip()
    return True


def _imported_device_count():
    """Device count of the already-imported jax, via ``sys.modules`` —
    never imports jax itself (this module's contract). Returns the
    string ``"?"`` when the backend cannot be asked (mid-import, broken
    install), so warning paths stay exception-free."""
    try:
        return sys.modules["jax"].device_count()
    except Exception:  # noqa: BLE001 — diagnostics must never raise
        return "?"


#: Environment contract of the multi-process launcher (DESIGN.md §13).
#: ``repro.launch.distributed.init_from_env`` reads these; the simulated
#: harness sets them on each worker it spawns. They live here so the
#: names have one jax-free home both sides import.
DIST_COORDINATOR = "REPRO_DIST_COORDINATOR"
DIST_NUM_PROCESSES = "REPRO_DIST_NUM_PROCESSES"
DIST_PROCESS_ID = "REPRO_DIST_PROCESS_ID"
DIST_LOCAL_DEVICES = "REPRO_DIST_LOCAL_DEVICES"


def distributed_env() -> dict | None:
    """Parse the ``REPRO_DIST_*`` worker environment, or None when unset.

    Returns ``{"coordinator": str, "num_processes": int,
    "process_id": int, "local_devices": int | None}``. Partial
    configuration raises — a worker with a coordinator but no process id
    would hang the whole barrier, so refusing early is the kind option.
    """
    coord = os.environ.get(DIST_COORDINATOR)
    if coord is None:
        if any(v in os.environ for v in (DIST_NUM_PROCESSES,
                                         DIST_PROCESS_ID)):
            raise ValueError(
                f"partial REPRO_DIST_* environment: {DIST_COORDINATOR} is "
                f"unset but process-topology variables are present")
        return None
    try:
        nproc = int(os.environ[DIST_NUM_PROCESSES])
        pid = int(os.environ[DIST_PROCESS_ID])
    except KeyError as e:
        raise ValueError(
            f"partial REPRO_DIST_* environment: {DIST_COORDINATOR} is set "
            f"but {e.args[0]} is missing") from None
    local = os.environ.get(DIST_LOCAL_DEVICES)
    return {"coordinator": coord, "num_processes": nproc, "process_id": pid,
            "local_devices": int(local) if local is not None else None}


#: Environment variable that places JAX's persistent compilation cache
#: (JAX itself reads it when it is imported).
COMPILATION_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
#: Where the cache goes when that variable is unset: a fixed path inside
#: the checkout. The directory is part of the cache key, so a temporary,
#: per-process or per-run name would never hit.
DEFAULT_COMPILATION_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set, and then no directory
    is set in code. Otherwise the cache goes to
    :data:`DEFAULT_COMPILATION_CACHE`. Entry points call this before
    their first compile; it imports jax, so call it after
    :func:`ensure_host_device_count`.
    """
    path = os.environ.get(COMPILATION_CACHE_ENV)
    if path:
        return path
    import jax

    path = str(DEFAULT_COMPILATION_CACHE)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
