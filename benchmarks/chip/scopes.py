"""The train step's device time per layer, and the program's compile counts.

The program names the layers of its train step with named scopes and
maps every instruction of the compiled step to one
(``Program.step_scopes()``: fwd, bwd, remat, opt, sync.encode,
sync.exchange, zero1.gather, unscoped); it counts its compilations in
``launch/compile_cache.py`` (``compile_stats()``).  ``reading`` takes
both once per cell, in this order: the compile counters first, so that
the map's own lowering is not counted; then the cell's program is built
again and its step lowered, the executable coming from the persistent
cache.  The ``*_ms`` functions lay that map over the traced tail.  Every
value is per step and averaged over the chips, as ``collectives.ms`` is.

Against a program that has neither, every value is None.  So is every
scope value where ops that the map does not name hold more than
``UNMAPPED_SHARE`` of busy time: the map then describes another
executable than the one traced.
"""
from __future__ import annotations

import sys
import time

from benchmarks.chip import tracing

UNMAPPED_SHARE = 0.01
SYNC = ("sync.encode", "sync.exchange")

_readings: dict = {}


def reading(run) -> dict:
    """``{"compile": compile_stats() or None, "scopes": {instruction:
    layer} or None, "self_ms": self_ms(...) or None}`` for the run's
    cell, taken once per cell."""
    name = run.cell.name
    if name not in _readings:
        _readings[name] = _take(run)
    return _readings[name]


def _take(run) -> dict:
    import jax

    try:
        from repro.launch.compile_cache import compile_stats
    except ImportError:
        compile_stats = None
    stats = compile_stats() if compile_stats else None
    from repro.train.build import Program as ProgramBundle

    mapping = None
    if hasattr(ProgramBundle, "step_scopes"):
        from benchmarks.chip import harness

        t0 = time.perf_counter()
        prog = harness.Program.build(run.cell, jax.devices()[:run.chips])
        mapping = prog.prog.step_scopes()
        print(f"[scopes] {len(mapping)} instructions mapped in "
              f"{time.perf_counter() - t0:.3f} s", file=sys.stderr, flush=True)
    per_layer = None
    if mapping is not None and run.trace is not None and run.traced_steps:
        per_layer = self_ms(run.trace, mapping, run.traced_steps)
    return {"compile": stats, "scopes": mapping, "self_ms": per_layer}


def self_ms(trace, mapping: dict, steps: int) -> dict | None:
    """``{layer: ms}`` of device self time per step, summed over the ops
    the map puts in each layer; None where unmapped ops hold more than
    ``UNMAPPED_SHARE`` of busy time."""
    devs = sorted(trace.ops)
    lo, hi = trace.window
    out, unmapped = {}, 0.0
    for d in devs:
        inside = [ev for ev in trace.ops[d] if ev[3] > lo and ev[2] < hi]
        for op, s in tracing.self_times(inside).items():
            if op in mapping:
                out[mapping[op]] = out.get(mapping[op], 0.0) + s
            else:
                unmapped += s
    if unmapped > UNMAPPED_SHARE * tracing.busy_s(trace) * len(devs):
        return None
    return {k: 1e3 * v / len(devs) / steps for k, v in out.items()}


def _in_flight(trace, mapping: dict, dev: int, layers) -> list:
    """Merged intervals in which an op of ``layers`` ran or was in flight
    (the XLA Ops and Async XLA Ops lines) on one device."""
    lo, hi = trace.window
    evs = trace.ops.get(dev, []) + trace.async_ops.get(dev, [])
    return tracing.union([(s, e) for name, _, s, e in evs
                          if mapping.get(name) in layers], lo, hi)


def in_flight_ms(trace, mapping: dict, steps: int, layer: str) -> float:
    """Milliseconds per step in which an op of ``layer`` was running or
    in flight."""
    devs = sorted(trace.ops)
    total = sum(tracing.length(_in_flight(trace, mapping, d, (layer,)))
                for d in devs)
    return total / len(devs) / steps / 1e6


def exposed_ms(trace, mapping: dict, steps: int) -> float:
    """Milliseconds per step of ``sync.exchange`` in flight while no op
    outside the sync layers ran on that device."""
    devs = sorted(trace.ops)
    lo, hi = trace.window
    total = 0.0
    for d in devs:
        exch = _in_flight(trace, mapping, d, ("sync.exchange",))
        other = tracing.union([(s, e) for name, _, s, e in trace.ops.get(d, [])
                               if mapping.get(name) not in SYNC], lo, hi)
        total += tracing.length(tracing.subtract(exch, other))
    return total / len(devs) / steps / 1e6


def layer_ms(run, layer: str):
    """A per-layer metric's reduction: ``layer``'s self time per step."""
    per_layer = reading(run)["self_ms"]
    return None if per_layer is None else per_layer.get(layer, 0.0)


def layer_in_flight_ms(run, layer: str):
    r = reading(run)
    if r["self_ms"] is None:
        return None
    return in_flight_ms(run.trace, r["scopes"], run.traced_steps, layer)


def sync_exposed_ms(run):
    r = reading(run)
    if r["self_ms"] is None:
        return None
    return exposed_ms(run.trace, r["scopes"], run.traced_steps)


def compile_value(run, key: str):
    """One number of the compile counters' snapshot (``executables``,
    ``seconds``), or None where the program keeps none."""
    stats = reading(run)["compile"]
    return None if stats is None else stats[key]
