"""Pallas-TPU kernels: hash-bitmap pack / unpack (Algorithm 2).

Pack: 32 occupancy bits -> one word via lane-shifted integer adds (VPU;
no MXU involvement — the bit weights exceed f32's exact range so a
matmul-with-weights formulation would be lossy).  The bits occupy
disjoint positions, so their int32 sum IS the packed word's bit pattern
(the top bit wraps to the sign); Mosaic reduces signed integers only, so
the kernels work in int32 and the wrappers bitcast to uint32.
Unpack: word >> lane & 1 with a broadcasted 2-D iota (TPU requires >=2D
iota).

Layout: bits [W, 32] int32 <-> words [W, 1] int32 (2-D so that a block
of BLOCK_W rows obeys the TPU tiling rule); W tiled by 128 rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BITS = 32
BLOCK_W = 128


def _pack_kernel(bits_ref, words_ref):
    bits = bits_ref[...]                                  # [BW, 32] int32
    lane = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
    words_ref[...] = jnp.sum(bits << lane, axis=1, keepdims=True)


def _unpack_kernel(words_ref, bits_ref):
    words = words_ref[...]                                # [BW, 1] int32
    lane = jax.lax.broadcasted_iota(jnp.int32, (words.shape[0], BITS), 1)
    bits_ref[...] = jax.lax.shift_right_logical(
        jnp.broadcast_to(words, lane.shape), lane) & 1


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_pack(bits: jnp.ndarray, *, interpret: bool = True) -> jnp.ndarray:
    """bits int32 0/1 [W, 32] -> uint32 [W]."""
    W = bits.shape[0]
    bw = min(BLOCK_W, W)
    assert W % bw == 0 and bits.shape[1] == BITS
    words = pl.pallas_call(
        _pack_kernel,
        grid=(W // bw,),
        in_specs=[pl.BlockSpec((bw, BITS), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((W, 1), jnp.int32),
        interpret=interpret,
    )(bits.astype(jnp.int32))
    return jax.lax.bitcast_convert_type(words[:, 0], jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_unpack(words: jnp.ndarray, *, interpret: bool = True) -> jnp.ndarray:
    """uint32 [W] -> bits int32 0/1 [W, 32]."""
    W = words.shape[0]
    bw = min(BLOCK_W, W)
    assert W % bw == 0
    w2 = jax.lax.bitcast_convert_type(words, jnp.int32)[:, None]
    return pl.pallas_call(
        _unpack_kernel,
        grid=(W // bw,),
        in_specs=[pl.BlockSpec((bw, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bw, BITS), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((W, BITS), jnp.int32),
        interpret=interpret,
    )(w2)
