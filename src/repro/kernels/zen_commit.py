"""Pallas-TPU megakernels: the fused Zen commit path (DESIGN.md §14).

The commit-side counterpart of ``zen_encode.py``'s encode megakernel.
Two kernels cover the server work of ``schemes.zen_commit``:

* **push fuse** — server aggregation (``scatter_add.py``), non-zero
  mask + compaction (``compact_indices``) and occupancy-bitmap packing
  (``bitmap.py``) become ONE kernel: the pushed (position, value) pairs
  enter VMEM once and the wire-format pull payload (compacted server
  positions, their values, the packed server bitmap, the pull overflow
  count) leaves once.  The 3-dispatch route materializes the
  ``[cap_server, d]`` aggregation buffer to HBM between every stage;
  here it never leaves VMEM.

* **pull fuse** — the batched decode of every server's gathered bitmap
  (``bitmap_unpack`` + ``compact_rows``) becomes one kernel with grid
  ``(n,)``: one step per server row, each unpacking its words and
  compacting the set-bit positions in a single VMEM pass.  The
  permutation gather and the final full-length apply stay in XLA — their
  output is the whole gradient, too large for a VMEM-resident kernel.

Bit-exactness contract: the push kernel scalar-prefetches the update
positions into SMEM and adds each update row into an f32 server buffer
in stream order — XLA's scatter-add order — rounding to the data dtype
after every add.  Mask, compaction (ascending, the ``compact_indices``
order, ranks from ``prefix.prefix_count``) and value gather all match
the XLA formulations word for word: the gather is a 0/1 selection matmul
on the MXU over exact bf16 terms of the values (one term for bf16 data,
three for f32), so each output row is one input row moved exactly.
Bitmap words are assembled as two 16-bit halves whose power-of-two
weights are exact in bf16 and whose sums stay exact in f32; the pull
kernel spreads words to positions one byte at a time the same way.  The
3-deep oracle hierarchy (fused → interpret-mode kernel → XLA composition
/ unfused chain) is CI-gated in tests/test_zen_commit_fused.py, and the
chip run in ``chip_smoke.py`` checks the compiled kernels bit-exact.
Denormal f32 values are the one exception the chip may flush.

VMEM envelope: the push kernel holds the ``[Csp, d]`` f32 server buffer,
the ``[Csp, Lp]`` selection matrix and the ``[Csp, Wp]`` bitmap weights;
the pull kernel ``[Wp, Csp]`` and ``[Csp, Lp]`` — sized by the compact
server buffer (Csp ~ M/n), not the gradient.  At micro_sync's gate point
(Csp 4224, Lp 384, d 128) they compile for v5e
(tests/test_tpu_compile.py); a server partition of a real-width
embedding table (tens of thousands of rows) exceeds the chip's VMEM and
the compiler refuses it.  Tiling the compaction over Csp blocks (the
prefix count is associative) is the open item in ROADMAP.md.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import EMPTY
from repro.kernels.prefix import column, prefix_count
from repro.kernels.prefix import row as row_of

LANES = 128
BITS = 32


def _bf16_parts(x: jnp.ndarray, dtype) -> list:
    """``x`` (f32, values representable in ``dtype``) as bf16 terms whose
    f32 sum is ``x`` exactly: one term for bf16 data, three for f32
    (3 x 8 significand bits cover f32's 24).  A 0/1 selection matmul over
    each term then moves values bit-exactly through the MXU."""
    if dtype == jnp.bfloat16:
        return [x.astype(jnp.bfloat16)]
    parts = []
    for _ in range(3):
        hi = x.astype(jnp.bfloat16)
        parts.append(hi)
        x = x - hi.astype(jnp.float32)
    return parts


def _push_kernel(lp_ref, val_ref, lpos_ref, vals_ref, occ_ref, ovf_ref,
                 buf_ref, *, cap_server: int, cap_pull: int):
    # --- server aggregation: sequential RMW over the update stream --------
    # lp is scalar-prefetched into SMEM; each update adds its row into the
    # f32 server buffer in stream order — the order XLA's scatter-add
    # applies duplicate indices — rounded to the data dtype after every
    # add.  Positions >= cap_server (the EMPTY sentinel and the pad) are
    # dropped.
    dtype = vals_ref.dtype
    buf_ref[...] = jnp.zeros(buf_ref.shape, jnp.float32)

    def body(c, carry):
        s = lp_ref[c]

        @pl.when((s >= 0) & (s < cap_server))
        def _():
            upd = buf_ref[pl.ds(s, 1), :] + val_ref[pl.ds(c, 1), :]
            buf_ref[pl.ds(s, 1), :] = upd.astype(dtype).astype(jnp.float32)

        return carry

    jax.lax.fori_loop(0, lp_ref.shape[0], body, 0)
    buf = buf_ref[...]                                    # [Csp, D] f32
    Csp = buf.shape[0]

    # --- mask + compaction (compact_indices formulation, ascending) -------
    mask = jnp.max((buf != 0).astype(jnp.int32), axis=1,
                   keepdims=True) > 0                     # [Csp, 1]
    pos = column(prefix_count(row_of(mask))) - 1          # [Csp, 1]
    nnz = jnp.sum(mask.astype(jnp.int32))
    Lp = lpos_ref.shape[1]
    jrow = jax.lax.broadcasted_iota(jnp.int32, (1, Lp), 1)
    srow = jax.lax.broadcasted_iota(jnp.int32, (Csp, 1), 0)
    hit2 = mask & (pos == jrow)                           # [Csp, Lp]
    comp = jnp.sum(jnp.where(hit2, srow, 0), axis=0, keepdims=True)
    kept = jnp.minimum(nnz, cap_pull)
    lpos_ref[...] = jnp.where(jrow < kept, comp, EMPTY)
    # one-hot value gather on the MXU: each output row selects at most
    # one buffer row, and every bf16 term moves exactly (see _bf16_parts)
    sel = hit2.astype(jnp.bfloat16)
    got = None
    for part in _bf16_parts(buf, dtype):
        term = jax.lax.dot_general(
            sel, part, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [Lp, D]
        got = term if got is None else got + term
    jcol = jax.lax.broadcasted_iota(jnp.int32, (Lp, 1), 0)
    vals_ref[...] = jnp.where(jcol < kept, got, 0).astype(dtype)
    ovf_ref[...] = jnp.broadcast_to(jnp.maximum(nnz - cap_pull, 0),
                                    ovf_ref.shape)

    # --- occupancy bitmap of the SERVER mask (not a prefix: pull decoders
    # re-derive positions from it).  Word w = sum of mask[32w+b] << b, as
    # two exact 16-bit halves: the weights are powers of two (exact in
    # bf16) and each half's sum stays below 2**16 (exact in f32) ---------
    Wp = occ_ref.shape[1]
    sidx = jax.lax.broadcasted_iota(jnp.int32, (Csp, Wp), 0)
    widx = jax.lax.broadcasted_iota(jnp.int32, (Csp, Wp), 1)
    b = sidx % BITS
    own = (sidx // BITS) == widx
    mrow = jnp.broadcast_to(row_of(mask).astype(jnp.bfloat16), (8, Csp))
    half = []
    for lo in (0, 16):
        wgt = jnp.where(own & (b >= lo) & (b < lo + 16),
                        (1 << jnp.clip(b - lo, 0, 15)).astype(jnp.float32),
                        0.0).astype(jnp.bfloat16)          # [Csp, Wp]
        half.append(jnp.dot(mrow, wgt, preferred_element_type=jnp.float32)[
            :1].astype(jnp.int32))                        # [1, Wp]
    occ_ref[...] = half[0] | (half[1] << 16)


@functools.partial(jax.jit,
                   static_argnames=("cap_server", "cap_pull", "interpret"))
def zen_commit_push_fused(lp: jnp.ndarray, vals: jnp.ndarray, *,
                          cap_server: int, cap_pull: int,
                          interpret: bool = True):
    """lp int32 [Cp] (entries outside [0, cap_server) are dropped), vals
    [Cp, d] -> (lpos [1, Lp], vals [Lp, d], occ uint32 [1, Wp], ovf
    [1, 1]) with Lp = cap_pull rounded up to LANES and Wp = ceil(cap_server
    / 32) rounded up so Wp*32 is a LANES multiple."""
    assert lp.ndim == 1 and lp.shape[0] == vals.shape[0]
    Cp = lp.shape[0]
    D = vals.shape[1]
    Lp = -(-cap_pull // LANES) * LANES
    Csp = -(-cap_server // LANES) * LANES
    Wp = Csp // BITS
    lpos, out, occ, ovf = pl.pallas_call(
        functools.partial(_push_kernel, cap_server=cap_server,
                          cap_pull=cap_pull),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((Cp, D), lambda i, lp: (0, 0))],
            out_specs=[
                pl.BlockSpec((1, Lp), lambda i, lp: (0, 0)),
                pl.BlockSpec((Lp, D), lambda i, lp: (0, 0)),
                pl.BlockSpec((1, Wp), lambda i, lp: (0, 0)),
                pl.BlockSpec((1, LANES), lambda i, lp: (0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((Csp, D), jnp.float32)],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((1, Lp), jnp.int32),
            jax.ShapeDtypeStruct((Lp, D), vals.dtype),
            jax.ShapeDtypeStruct((1, Wp), jnp.int32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(lp.astype(jnp.int32), vals.astype(jnp.float32))
    return (lpos, out, jax.lax.bitcast_convert_type(occ, jnp.uint32),
            ovf[:, :1])


def _pull_kernel(words_ref, lpos_ref, *, cap_server: int, cap_pull: int):
    w = words_ref[...]                                    # [1, Wp] int32
    Wp = w.shape[1]
    Csp = Wp * BITS
    # --- unpack: spread each word over its 32 positions with a 0/1
    # selection matmul, one byte at a time (bytes are exact in bf16) ------
    spread = (jax.lax.broadcasted_iota(jnp.int32, (Wp, Csp), 1) // BITS
              == jax.lax.broadcasted_iota(jnp.int32, (Wp, Csp), 0)
              ).astype(jnp.bfloat16)                      # [Wp, Csp]
    col = jax.lax.broadcasted_iota(jnp.int32, (1, Csp), 1)  # position s
    b = col % BITS
    bit = jnp.zeros((1, Csp), jnp.int32)
    for k in range(4):
        byte = jax.lax.shift_right_logical(w, 8 * k) & 0xFF
        byte = jnp.broadcast_to(byte.astype(jnp.bfloat16), (8, Wp))
        at = jnp.dot(byte, spread, preferred_element_type=jnp.float32)[
            :1].astype(jnp.int32)                         # [1, Csp]
        sh = jnp.clip(b - 8 * k, 0, 7)
        bit = jnp.where(b // 8 == k,
                        jax.lax.shift_right_logical(at, sh) & 1, bit)
    live = (bit == 1) & (col < cap_server)                # trim pad bits
    # --- compaction of the set positions, ascending ------------------------
    pos = column(prefix_count(live)) - 1                  # [Csp, 1]
    nnz = jnp.sum(live.astype(jnp.int32))
    Lp = lpos_ref.shape[1]
    jrow = jax.lax.broadcasted_iota(jnp.int32, (1, Lp), 1)
    hit = column(live) & (pos == jrow)                    # [Csp, Lp]
    comp = jnp.sum(jnp.where(hit, column(col), 0), axis=0, keepdims=True)
    kept = jnp.minimum(nnz, cap_pull)
    lpos_ref[...] = jnp.where(jrow < kept, comp, EMPTY)


@functools.partial(jax.jit,
                   static_argnames=("cap_server", "cap_pull", "interpret"))
def zen_commit_pull_fused(words: jnp.ndarray, *, cap_server: int,
                          cap_pull: int, interpret: bool = True):
    """words uint32 [n, Wp] (per-server gathered bitmaps, Wp*32 a LANES
    multiple) -> lpos int32 [n, Lp]: each row's set-bit positions below
    ``cap_server``, compacted ascending and EMPTY-padded, first
    ``cap_pull`` kept.  Lp = cap_pull rounded up to LANES."""
    assert words.ndim == 2 and (words.shape[1] * BITS) % LANES == 0
    n, Wp = words.shape
    Lp = -(-cap_pull // LANES) * LANES
    w3 = jax.lax.bitcast_convert_type(words, jnp.int32)[:, None, :]
    return pl.pallas_call(
        functools.partial(_pull_kernel, cap_server=cap_server,
                          cap_pull=cap_pull),
        grid=(n,),
        in_specs=[pl.BlockSpec((None, 1, Wp), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, Lp), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1, Lp), jnp.int32),
        interpret=interpret,
    )(w3)[:, 0]
