"""Pallas-TPU kernel: server-side COO row aggregation (scatter-add).

The Pull-side hot loop of Zen: accumulate pushed (index, row-value) pairs
into the server's compact partition buffer.  On GPU this is atomicAdd; on
TPU the *sequential* grid makes read-modify-write race-free, so the kernel
is a plain RMW loop over the entries — the TPU-idiomatic equivalent
(DESIGN.md §3).

Layout: the indices are scalar-prefetched into SMEM (the scalar core reads
one per iteration); the values ``[C, d]`` sit in VMEM once; the output is
tiled into ``BLOCK_M``-row blocks, and each grid step walks the whole
index stream in order and applies the entries that land in its block.
Per output row the adds therefore happen in stream order — XLA's
scatter-add order — so the result is bit-identical to it.  Accumulation
runs in an f32 scratch rounded back to the output dtype after every add
(bf16 rows cannot be addressed one at a time; an f32 add of two bf16
values rounded to bf16 is the bf16 add).

The result is a new buffer: the kernel does not alias ``out`` (an
aliased operand that is a caller's live array would need a copy anyway,
and reading the accumulation base from the input block keeps the
kernel's view of it well defined).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.hashing import EMPTY

BLOCK_M = 512  # output rows per grid step


def _kernel(idx_ref, vals_ref, base_ref, out_ref, acc_ref, *, rows: int):
    # an output block starts as whatever VMEM held on the chip: the
    # block's starting contents come from the input block
    bm = acc_ref.shape[0]
    lo = pl.program_id(0) * bm
    acc_ref[...] = base_ref[...].astype(jnp.float32)

    def body(i, carry):
        ix = idx_ref[i]
        local = ix - lo

        @pl.when((ix != EMPTY) & (ix >= 0) & (ix < rows)
                 & (local >= 0) & (local < bm))
        def _():
            upd = acc_ref[pl.ds(local, 1), :] + vals_ref[pl.ds(i, 1), :]
            acc_ref[pl.ds(local, 1), :] = upd.astype(out_ref.dtype).astype(
                jnp.float32)

        return carry

    jax.lax.fori_loop(0, idx_ref.shape[0], body, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def coo_scatter_add(out: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray,
                    *, interpret: bool = True) -> jnp.ndarray:
    """out [M, d] += scatter(vals [C, d] at idx [C]); returns new out.

    EMPTY / out-of-range indices are dropped.
    """
    C = idx.shape[0]
    M, d = out.shape
    bm = min(BLOCK_M, -(-M // 8) * 8)
    pad = (-M) % bm
    outp = jnp.pad(out, ((0, pad), (0, 0)))
    res = pl.pallas_call(
        functools.partial(_kernel, rows=M),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(outp.shape[0] // bm,),
            in_specs=[
                pl.BlockSpec((C, d), lambda i, ix: (0, 0)),
                pl.BlockSpec((bm, d), lambda i, ix: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bm, d), lambda i, ix: (i, 0)),
            scratch_shapes=[pltpu.VMEM((bm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(outp.shape, out.dtype),
        interpret=interpret,
    )(idx.astype(jnp.int32), vals.astype(jnp.float32), outp)
    return res[:M]
