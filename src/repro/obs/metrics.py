"""Unified metrics registry + run metadata for the bench trajectory.

Before this module every metrics source was ad hoc: kernel LRU
``cache_info()``, per-bucket engine ``compile_counts()``, packing
``batch_stats()`` fill histograms, service early-stop counters -- each with
its own accessor and no common envelope.  :class:`MetricsRegistry` puts
them behind one ``snapshot()`` with a pinned top-level schema
(:data:`SNAPSHOT_KEYS`), so ``PropagationService.stats()`` and the bench's
``obs`` row report through a single shape.

Sources are zero-arg callables registered by name; a failing source lands
in ``errors`` instead of taking the snapshot down -- observability must
never crash the thing it observes.

:func:`run_metadata` stamps every ``BENCH_prop.json`` merge with the
environment that produced it (git commit, timestamp, jax version, x64
flag, backend), turning the bench file from unversioned snapshots into an
attributable trajectory.
"""
from __future__ import annotations

import datetime
import subprocess
import threading

#: Pinned top-level snapshot schema.
SNAPSHOT_KEYS = frozenset({"schema_version", "sources", "errors"})

#: Schema version stamped into snapshots (bump on any SNAPSHOT_KEYS change).
SNAPSHOT_SCHEMA_VERSION = 1


class MetricsRegistry:
    """Named zero-arg metric sources behind one pinned-schema snapshot.

    ``register(name, fn)`` adds a source whose ``fn()`` returns any
    JSON-able value; ``snapshot()`` evaluates them all under the pinned
    envelope ``{schema_version, sources, errors}``.  Thread-safe: sources
    may be registered while another thread snapshots.
    """

    def __init__(self):
        self._sources: dict[str, object] = {}
        self._lock = threading.Lock()

    def register(self, name: str, fn, replace: bool = False):
        """Add source ``name`` -> ``fn()``; re-registering needs ``replace``."""
        with self._lock:
            if name in self._sources and not replace:
                raise ValueError(f"metrics source already registered: {name!r}")
            self._sources[name] = fn

    def unregister(self, name: str):
        """Remove a source (missing names are a no-op)."""
        with self._lock:
            self._sources.pop(name, None)

    def source_names(self) -> tuple:
        """Registered source names, sorted."""
        with self._lock:
            return tuple(sorted(self._sources))

    def snapshot(self) -> dict:
        """Evaluate every source: ``{schema_version, sources, errors}``.

        A source that raises contributes ``errors[name] = repr(exc)``
        rather than propagating -- one broken gauge never blinds the rest.
        """
        with self._lock:
            items = list(self._sources.items())
        sources, errors = {}, {}
        for name, fn in items:
            try:
                sources[name] = fn()
            except Exception as e:  # noqa: BLE001 -- isolation is the contract
                errors[name] = repr(e)
        return {
            "schema_version": SNAPSHOT_SCHEMA_VERSION,
            "sources": sources,
            "errors": errors,
        }


def default_registry() -> MetricsRegistry:
    """Registry preloaded with the process-wide sources every run has:
    kernel LRU ``cache_info()`` and the program tracer's totals
    (``program``: per-span-name counts and seconds, per-site counters such
    as ``h2d_bytes``).  Callers (the service constructor, the bench's
    ``obs`` row) add their own instance-scoped sources on top."""
    from ..kernels.ops import cache_info  # lazy: kernels pulls in jax state
    from .trace import PROGRAM

    reg = MetricsRegistry()
    reg.register("kernel_caches", cache_info)
    reg.register(
        "program", lambda: {"spans": PROGRAM.totals(), "counters": PROGRAM.counters()}
    )
    return reg


def run_metadata() -> dict:
    """Environment fingerprint for a bench merge: commit, time, jax, backend.

    Never raises -- a missing git binary or detached worktree degrades the
    commit field to ``"unknown"`` so benches keep running anywhere.
    """
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=5, check=True,
        ).stdout.strip() or "unknown"
    except Exception:
        commit = "unknown"
    try:
        import jax

        jax_version = jax.__version__
        x64 = bool(jax.config.jax_enable_x64)
        backend = jax.default_backend()
    except Exception:
        jax_version, x64, backend = "unknown", False, "unknown"
    return {
        "git_commit": commit,
        "timestamp": datetime.datetime.now(datetime.timezone.utc)
        .isoformat(timespec="seconds"),
        "jax_version": jax_version,
        "x64": x64,
        "backend": backend,
    }
