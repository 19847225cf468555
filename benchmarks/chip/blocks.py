"""The train step's device time per model block.

The program maps every instruction of its compiled step to a block
(``Program.step_blocks()``: ``moe.route``, ``moe.experts`` or ``other``),
whichever layer (fwd, remat, bwd) the instruction runs in.  ``reading``
takes that map once per cell, after the traced tail (the cell's program
is built again and its step lowered, the executable coming from the
persistent cache), and lays it over the traced tail as ``scopes.self_ms``
lays the layer map: device self time per step, averaged over the chips.

Against a program that has no block map the values are None, and so they
are where ops the map does not name hold more than
``scopes.UNMAPPED_SHARE`` of busy time.
"""
from __future__ import annotations

import sys
import time

from benchmarks.chip import scopes

_readings: dict = {}


def reading(run) -> dict | None:
    """``{block: ms per step}`` of the run's traced tail, or None."""
    name = run.cell.name
    if name not in _readings:
        _readings[name] = _take(run)
    return _readings[name]


def _take(run) -> dict | None:
    import jax

    from repro.train.build import Program as ProgramBundle

    if (not hasattr(ProgramBundle, "step_blocks") or run.trace is None
            or not run.traced_steps):
        return None
    from benchmarks.chip import harness

    t0 = time.perf_counter()
    prog = harness.Program.build(run.cell, jax.devices()[:run.chips])
    mapping = prog.prog.step_blocks()
    print(f"[blocks] {len(mapping)} instructions mapped in "
          f"{time.perf_counter() - t0:.3f} s", file=sys.stderr, flush=True)
    return scopes.self_ms(run.trace, mapping, run.traced_steps)


def block_ms(run, block: str):
    """A per-block metric's reduction: ``block``'s self time per step."""
    per_block = reading(run)
    return None if per_block is None else per_block.get(block, 0.0)
