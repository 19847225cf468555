"""Pallas-TPU fused flash-attention kernel (forward).

This is the §Perf optimization that removes the dominant HBM term from the
baseline roofline: the jnp-level online-softmax (layers.flash_attention)
materializes the [Bq, chunk] score/probability blocks in HBM every
(q-block x kv-chunk) step; this kernel keeps them in VMEM.

Grid: (batch*kv_head*group, q_blocks); each program owns one q block and
iterates kv blocks with `lax.fori_loop`, carrying (m, l, o) in VMEM scratch.
Block shapes are MXU-aligned ((BQ, hd) x (hd, BK) matmuls with hd, BQ, BK
multiples of 128 where possible).  Validated against ``ref.py`` /
``layers.flash_attention`` in interpret mode (CPU) — on TPU pass
``interpret=False``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bk: int, causal: bool,
            window: int, scale: float):
    """One q-block vs all kv-blocks. q [BQ, hd]; k/v [Sk, hd]; o [BQ, hd]."""
    qi = pl.program_id(1)
    BQ, hd = q_ref.shape
    Sk = k_ref.shape[0]
    q = q_ref[...].astype(jnp.float32) * scale
    pos_q = qi * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, 1), 0)[:, 0]

    def body(ci, carry):
        m, l, o = carry
        k = k_ref[pl.ds(ci * bk, bk), :]
        v = v_ref[pl.ds(ci * bk, bk), :]
        s = q @ k.astype(jnp.float32).T                      # [BQ, bk] VMEM
        pos_k = ci * bk + jax.lax.broadcasted_iota(
            jnp.int32, (1, bk), 1)[0]
        valid = pos_k[None, :] < Sk
        if causal:
            valid = valid & (pos_k[None, :] <= pos_q[:, None])
        if window > 0:
            valid = valid & (pos_k[None, :] > pos_q[:, None] - window)
        s = jnp.where(valid, s, NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[:, None] + p @ v.astype(jnp.float32)
        return m_new, l_new, o_new

    nk = (Sk + bk - 1) // bk
    m0 = jnp.full((BQ,), NEG, jnp.float32)
    l0 = jnp.zeros((BQ,), jnp.float32)
    o0 = jnp.zeros((BQ, hd), jnp.float32)
    m, l, o = jax.lax.fori_loop(0, nk, body, (m0, l0, o0))
    o_ref[...] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal", "window",
                                             "interpret"))
def flash_fwd(q, k, v, *, bq: int = 256, bk: int = 256, causal: bool = True,
              window: int = 0, interpret: bool = True):
    """q [B, Sq, H, hd]; k, v [B, Sk, KV, hd] with H % KV == 0.

    Returns [B, Sq, H, hd].  Score blocks never leave VMEM.
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    bq = min(bq, Sq)
    assert Sq % bq == 0, (Sq, bq)
    # collapse (B, KV, g) into the grid's major axis
    qg = q.reshape(B, Sq, KV, g, hd).transpose(0, 2, 3, 1, 4) \
          .reshape(B * KV * g, Sq, hd)
    kg = jnp.broadcast_to(k.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KV, g, Sk, hd)).reshape(B * KV * g, Sk, hd)
    vg = jnp.broadcast_to(v.transpose(0, 2, 1, 3)[:, :, None],
                          (B, KV, g, Sk, hd)).reshape(B * KV * g, Sk, hd)
    grid = (B * KV * g, Sq // bq)
    out = pl.pallas_call(
        functools.partial(_kernel, bk=bk, causal=causal, window=window,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bq, hd), lambda h, i: (h, i, 0)),
            pl.BlockSpec((None, Sk, hd), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((None, Sk, hd), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, hd), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * KV * g, Sq, hd), q.dtype),
        interpret=interpret,
    )(qg, kg, vg)
    return out.reshape(B, KV, g, Sq, hd).transpose(0, 3, 1, 2, 4) \
              .reshape(B, Sq, H, hd)
