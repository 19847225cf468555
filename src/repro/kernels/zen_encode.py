"""Pallas-TPU megakernel: the fused Zen encode path (DESIGN.md §11).

Fuses the three encode dispatches — hash stage (``hash_stage.py``), the k
round-synchronous insertion rounds + serial memory (previously XLA
scatter_min in ``hashing.hierarchical_hash``), row extraction
(``compact.py``) and occupancy bitmap packing (``bitmap.py``) — into ONE
kernel: gradient indices enter VMEM once and the wire-format outputs
(compacted per-partition indices, packed occupancy bitmap, overflow count)
leave once.  The 3-dispatch route materializes the n x (r1+r2) index memory
to HBM twice (after hashing, after compaction); here it never leaves VMEM.

Key structural fact that makes the fusion a clean grid: every insertion
round, the serial-memory placement, the extraction and the bitmap of an
index happen entirely within its h0-partition's row.  So the grid is
``(n,)`` — one step per partition, each step fully self-contained:

  grid step i:
    in   idx   [1, Cp]   the whole EMPTY-padded index set (same block
                         every step; partitions filter by h0)
    out  pidx  [1, Lp]   partition i's compacted indices (slot order)
    out  occ   [1, Wp]   occupancy bitmap of the compacted row (the uint32
                         words' int32 bit patterns)
    out  ovf   [1, 128]  partition i's serial-memory overflow count

The outputs are ``[n, 1, X]`` arrays written through squeezed
``(None, 1, X)`` blocks, so each block's last two dims equal the array's
(the TPU tiling rule that a ``(1, X)`` block of an ``[n, X]`` array
breaks).  Indices are turned into a ``[Cp, 1]`` column once so that the
proposal matrices are ``[candidates, slots]``; ranks and compaction
positions come from ``prefix.prefix_count`` (Mosaic has no cumsum).

Race-free by construction: the scatter_min race of the XLA path becomes a
per-slot min-reduction over an explicit [Cp, Lp] proposal matrix (the same
O(C·L) vectorized-ALU trade as ``compact.py``'s hit matrix), and the
"write-and-read" collision check becomes an exact winner test — indices are
unique (they come from ``compact_indices``), so ``idx == min(proposals)``
identifies the winner with no ties.  Bit-exactness vs both oracles
(interpret-mode 3-dispatch and XLA composition) is CI-gated
(tests/test_zen_encode_fused.py).

VMEM envelope: the proposal/hit matrices are [Cp, Lp] and [Lp, Lp] int32
per grid step.  For the capacity recipe (r1 = 2|I|/n, r2 = r1/10) that is
~(2|I|/n)² words — at micro_sync's gate point (Cp 768, Lp 384) about
1.2 MB, and it compiles for v5e (tests/test_tpu_compile.py).  A single
bucket with tens of thousands of non-zeros per partition (a real-width
embedding table) exceeds the chip's VMEM and the compiler refuses it;
tiling the round loop over Cp blocks (the reduction is associative) is
the open item in ROADMAP.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashing import EMPTY
from repro.kernels.hash_stage import _hash_u32
from repro.kernels.prefix import column, prefix_count
from repro.kernels.prefix import row as row_of

LANES = 128
BITS = 32


def _kernel(idx_ref, pidx_ref, occ_ref, ovf_ref, *,
            seeds: tuple, n: int, r1: int, r2: int):
    i = pl.program_id(0)
    idx = column(idx_ref[...])                            # [Cp, 1] int32
    valid = idx != EMPTY
    # --- hash stage: h0 picks the partition; this step keeps only its own --
    p = (_hash_u32(idx, seeds[0]) % jnp.uint32(n)).astype(jnp.int32)
    pending = valid & (p == i)                            # [Cp, 1]

    Lp = pidx_ref.shape[1]
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, Lp), 1)  # [1, Lp]
    row = jnp.full((1, Lp), EMPTY, dtype=jnp.int32)

    # --- k round-synchronous insertion rounds (Alg. 1 parallel memory) -----
    # cand[c, l]: pending index c proposes slot l this round; prop masks to
    # currently-empty slots; the per-slot min over proposers IS scatter_min.
    for s in seeds[1:]:
        q = (_hash_u32(idx, s) % jnp.uint32(r1)).astype(jnp.int32)  # [Cp, 1]
        cand = pending & (q == slot)
        prop = cand & (row == EMPTY)                      # [Cp, Lp]
        m = jnp.min(jnp.where(prop, idx, EMPTY), axis=0, keepdims=True)
        won = jnp.max((prop & (m == idx)).astype(jnp.int32), axis=1,
                      keepdims=True) > 0                  # [Cp, 1]
        row = jnp.minimum(row, m)
        pending = pending & ~won

    # --- serial memory: prefix rank ≙ the paper's atomicAdd counter --------
    surv = pending
    rank = column(prefix_count(row_of(surv))) - 1         # [Cp, 1]
    fits = surv & (rank < r2)
    tgt = r1 + rank
    hit = fits & (tgt == slot)
    srow = jnp.min(jnp.where(hit, idx, EMPTY), axis=0, keepdims=True)
    row = jnp.minimum(row, srow)
    ovf = jnp.sum((surv & ~fits).astype(jnp.int32))
    ovf_ref[...] = jnp.broadcast_to(ovf, ovf_ref.shape)

    # --- extraction: order-preserving compaction (compact.py formulation) --
    lvalid = row != EMPTY
    pos = prefix_count(lvalid) - 1
    nnz = jnp.sum(lvalid.astype(jnp.int32))
    hit2 = column(lvalid) & (column(pos) == slot)         # [Lp, Lp]
    comp = jnp.sum(jnp.where(hit2, column(row), 0), axis=0, keepdims=True)
    pidx_ref[...] = jnp.where(slot < nnz, comp, EMPTY)

    # --- occupancy bitmap of the COMPACTED row: a prefix of nnz ones, so
    # word w holds min(max(nnz - 32w, 0), 32) low bits (int32 pattern) ----
    Wp = occ_ref.shape[1]
    w = jax.lax.broadcasted_iota(jnp.int32, (1, Wp), 1)
    nbits = jnp.clip(nnz - w * BITS, 0, BITS)
    occ_ref[...] = jnp.where(nbits == BITS, -1,
                             (1 << jnp.minimum(nbits, BITS - 1)) - 1)


@functools.partial(jax.jit,
                   static_argnames=("seeds", "n", "r1", "r2", "interpret"))
def zen_encode_fused(indices: jnp.ndarray, *, seeds: tuple, n: int,
                     r1: int, r2: int, interpret: bool = True):
    """indices int32 [1, Cp] (EMPTY-padded, Cp a LANES multiple) ->
    (pidx [n, Lp], occ uint32 [n, Lp/32], ovf [n, 1]) with Lp = r1+r2
    rounded up to LANES.  ``seeds``: k+1 compile-time python ints."""
    assert indices.ndim == 2 and indices.shape[0] == 1
    assert indices.shape[1] % LANES == 0
    L = r1 + r2
    Lp = -(-L // LANES) * LANES
    Wp = Lp // BITS
    Cp = indices.shape[1]
    pidx, occ, ovf = pl.pallas_call(
        functools.partial(_kernel, seeds=seeds, n=n, r1=r1, r2=r2),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, Cp), lambda i: (0, 0))],
        out_specs=[
            pl.BlockSpec((None, 1, Lp), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, Wp), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1, Lp), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, Wp), jnp.int32),
            jax.ShapeDtypeStruct((n, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(indices)
    occ = jax.lax.bitcast_convert_type(occ[:, 0], jnp.uint32)
    return pidx[:, 0], occ, ovf[:, 0, :1]
