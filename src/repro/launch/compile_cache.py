"""Persistent XLA compilation cache for the entry points, and counters on
every compilation of the process.

``enable_compile_cache()`` is called by ``launch/train.py`` and
``chip_smoke.py`` at start-up, never on import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, the cache lives there and nowhere
else; otherwise it lives at one fixed path inside the checkout
(``.jax_cache/``, gitignored).  The path is part of what a cached entry
is found by, so it never depends on a temp name, a pid or the time.

``compile_stats()`` reads what JAX's compile events have reported since
this module was imported (listeners registered once, on import; they
run only when JAX compiles, never on a step's path).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
# fires once per executable obtained, compiled or read from the persistent
# cache: it wraps the cache lookup, so on a hit its time holds the
# retrieval's
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
EVENTS = (TRACE_EVENT, LOWER_EVENT, BACKEND_EVENT, CACHE_EVENT)

_seconds = dict.fromkeys(EVENTS, 0.0)
_counts = dict.fromkeys(EVENTS, 0)


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _seconds:
        _seconds[event] += duration
        _counts[event] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)


def compile_stats() -> dict:
    """``executables``: executables the process obtained (each backend
    compile or persistent-cache hit); ``cache_hits``: those read from the
    cache; ``seconds``: tracing + lowering + backend time (a hit's
    retrieval lies inside its backend time, so it is not added again);
    ``events``: the seconds of each event."""
    return {"executables": _counts[BACKEND_EVENT],
            "cache_hits": _counts[CACHE_EVENT],
            "seconds": (_seconds[TRACE_EVENT] + _seconds[LOWER_EVENT]
                        + _seconds[BACKEND_EVENT]),
            "events": dict(_seconds)}


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    if set, else at ``<checkout>/.jax_cache``; returns the directory."""
    path = os.environ.get(ENV) or str(DEFAULT_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
