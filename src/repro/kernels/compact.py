"""Pallas-TPU kernel: sort-free row compaction (stream compaction).

Moves each row's live (non-``EMPTY``) entries to the front, preserving their
slot order, and pads the tail with ``EMPTY``.  This is the extraction step of
Alg. 1 (lines 19-23) and the static-shape replacement for ``nonzero()`` —
previously done with a per-row ``argsort`` (O(L log L) and a ``sort`` op in
the HLO), now sort-free (see DESIGN.md §3).

Formulation: for an output column j, the value is the unique live input entry
whose prefix-count equals j.  Rather than a serial dynamic-index store loop
(L sequential RMWs — slow on TPU), the kernel reduces a [L, BLOCK_J] hit
matrix per output tile on the VPU.  That is O(L²) integer ALU work per row —
deliberately trading ops for full vectorization, which wins for the r1+r2 ~
1e3 row lengths the capacity recipe produces but grows quadratically beyond
that (the jnp path in ``hashing.row_compact`` stays O(L); prefer it if rows
get long).  Integer adds are exact, so no MXU/f32 precision concerns apply.

Layout: mem [R, L] int32 viewed as [R, 1, L]; grid (R, L / BLOCK_J); each
step reads a full row (a squeezed ``(None, 1, L)`` block, so the block's
last two dims equal the array's — the TPU tiling rule) and writes one
BLOCK_J-wide output tile.  The prefix rank is ``prefix.prefix_count``
(Mosaic has no cumsum).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import EMPTY
from repro.kernels.prefix import column, prefix_count

BLOCK_J = 128


def _kernel(mem_ref, out_ref):
    row = mem_ref[...]                                   # [1, L] int32
    valid = row != EMPTY
    pos = prefix_count(valid) - 1                        # prefix rank per entry
    nnz = jnp.sum(valid.astype(jnp.int32))
    j0 = pl.program_id(1) * BLOCK_J
    jcol = j0 + jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK_J), 1)  # [1, BJ]
    # hit[i, j]: live entry i lands in output column j
    hit = column(valid) & (column(pos) == jcol)                       # [L, BJ]
    vals = jnp.sum(jnp.where(hit, column(row), 0), axis=0, keepdims=True)
    out_ref[...] = jnp.where(jcol < nnz, vals, EMPTY)


@functools.partial(jax.jit, static_argnames=("interpret",))
def row_compact(mem: jnp.ndarray, *, interpret: bool = True) -> jnp.ndarray:
    """mem int32 [R, L] (L a BLOCK_J multiple) -> order-preserving compaction."""
    R, L = mem.shape
    assert L % BLOCK_J == 0, "pad columns to a BLOCK_J multiple (ops.row_compact_op does)"
    return pl.pallas_call(
        _kernel,
        grid=(R, L // BLOCK_J),
        in_specs=[pl.BlockSpec((None, 1, L), lambda i, j: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, BLOCK_J), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((R, 1, L), jnp.int32),
        interpret=interpret,
    )(mem[:, None, :])[:, 0, :]
