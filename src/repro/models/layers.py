"""Primitive layers with explicit tensor parallelism (Megatron-style).

All functions take the local parameter shard and a ``ShardCtx``; collectives
over the ``model`` axis are explicit (`psum` after row-parallel matmuls,
max/sum-reductions for vocab-sharded softmax).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.models.common import (ParamBuilder, ShardCtx, zero_rows_from)

# ---------------------------------------------------------------------------
# Norms (replicated)
# ---------------------------------------------------------------------------

def init_rmsnorm(b: ParamBuilder, name: str, d: int):
    b.ones(name, (d,), P(None), dtype=jnp.float32)


def rmsnorm(scale, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def rmsnorm_sharded(scale_local, x_local, ctx: "ShardCtx", eps=1e-5):
    """RMSNorm over a model-sharded feature dim: exact full-dim variance via
    psum; ``scale_local`` is this rank's slice (spec P('model'))."""
    xf = x_local.astype(jnp.float32)
    full = x_local.shape[-1] * max(ctx.tp, 1)
    var = ctx.psum_tp(jnp.sum(xf * xf, axis=-1, keepdims=True)) / full
    return (xf * jax.lax.rsqrt(var + eps) * scale_local).astype(x_local.dtype)


# ---------------------------------------------------------------------------
# Column / row parallel linear
# ---------------------------------------------------------------------------

def init_linear(b: ParamBuilder, name: str, d_in: int, d_out: int, *,
                mode: str, tp: int, bias: bool = False, scale=None):
    """mode: 'col' shards d_out, 'row' shards d_in, 'rep' replicates."""
    if mode == "col":
        assert d_out % tp == 0, (name, d_out, tp)
        spec_w, spec_b = P(None, "model"), P("model")
    elif mode == "row":
        assert d_in % tp == 0, (name, d_in, tp)
        spec_w, spec_b = P("model", None), P(None)
    else:
        spec_w, spec_b = P(None, None), P(None)
    b.dense(f"{name}_w", (d_in, d_out), spec_w, scale=scale)
    if bias:
        b.zeros(f"{name}_b", (d_out,), spec_b)


def linear_col(p, name, x):
    """Column-parallel: out feature dim is sharded; no collective."""
    y = x @ p[f"{name}_w"]
    if f"{name}_b" in p:
        y = y + p[f"{name}_b"]
    return y


def linear_row(p, name, x, ctx: ShardCtx):
    """Row-parallel: contraction dim is sharded; psum over model."""
    y = ctx.psum_tp(x @ p[f"{name}_w"])
    if f"{name}_b" in p:
        y = y + p[f"{name}_b"]
    return y


def linear_rep(p, name, x):
    y = x @ p[f"{name}_w"]
    if f"{name}_b" in p:
        y = y + p[f"{name}_b"]
    return y


# ---------------------------------------------------------------------------
# Vocab-parallel embedding + LM head (+ sharded cross-entropy)
# ---------------------------------------------------------------------------

def init_embedding(b: ParamBuilder, name: str, vocab: int,
                   vocab_padded: int, d: int):
    """Embedding table, vocab-sharded over model.

    The table is the row-sparse gradient tensor Zen synchronizes — the leaf
    path must match ``GradSync.sparse_paths`` (we use '<name>/table').

    Padding rows [vocab:vocab_padded) are zero-initialized: padded ids are
    never produced by the pipeline, so a non-zero init there would be dead
    weight that (tied or head-side) could leak into the sharded logsumexp.
    Their gradient is identically zero, so they never show up as non-zero
    rows in the Zen encode or the measured d(1)/d(n) densities.
    """
    sub = b.child(name)
    sub.dense("table", (vocab_padded, d), P("model", None), scale=0.02)
    zero_rows_from(sub, "table", vocab)


def embed_lookup(p, name, tokens, ctx: ShardCtx):
    """tokens [B, S] -> [B, S, d]; table local shard is [Vp/tp, d]."""
    table = p[name]["table"]
    v_local = table.shape[0]
    off = ctx.tp_rank() * v_local if ctx.tp > 1 else 0
    local = tokens - off
    ok = (local >= 0) & (local < v_local)
    safe = jnp.clip(local, 0, v_local - 1)
    out = table[safe] * ok[..., None].astype(table.dtype)
    return ctx.psum_tp(out)


def mask_padded_logits(lf, ctx: ShardCtx, valid_vocab: int | None):
    """Set logits of padded vocab columns (global id >= ``valid_vocab``) to
    ``NEG`` so they vanish from logsumexp/argmax and carry zero gradient —
    padding must never change the loss, the sampled token, or the gradients
    feeding the sync path (DESIGN.md §9)."""
    if valid_vocab is None:
        return lf
    v_local = lf.shape[-1]
    off = ctx.tp_rank() * v_local if ctx.tp > 1 else 0
    ok = (off + jnp.arange(v_local)) < valid_vocab
    return jnp.where(ok, lf, jnp.asarray(NEG, lf.dtype))


def cross_entropy_parts(logits_l, labels, ctx: ShardCtx, mask=None, *,
                        valid_vocab: int | None = None):
    """(nll_sum, token_count) over vocab-sharded logits [.., V/tp].

    ``valid_vocab`` excludes padded vocab columns from the logsumexp (and
    from the gradient); labels must always be < valid_vocab."""
    lf = mask_padded_logits(logits_l.astype(jnp.float32), ctx, valid_vocab)
    v_local = lf.shape[-1]
    # stop_gradient: the max shift is purely for numerical stability, and
    # pmax has no differentiation rule (its "gradient" would cancel anyway).
    m = lax.stop_gradient(ctx.pmax_tp(jnp.max(lf, axis=-1)))
    se = ctx.psum_tp(jnp.sum(jnp.exp(lf - m[..., None]), axis=-1))
    lse = jnp.log(se) + m
    off = ctx.tp_rank() * v_local if ctx.tp > 1 else 0
    loc = labels - off
    ok = (loc >= 0) & (loc < v_local)
    safe = jnp.clip(loc, 0, v_local - 1)
    picked = jnp.take_along_axis(lf, safe[..., None], axis=-1)[..., 0]
    correct = ctx.psum_tp(picked * ok.astype(jnp.float32))
    nll = lse - correct
    mf = (jnp.ones_like(nll) if mask is None
          else mask.astype(jnp.float32))
    return jnp.sum(nll * mf), jnp.sum(mf)


def cross_entropy_sharded(logits_l, labels, ctx: ShardCtx, *, mask=None,
                          valid_vocab: int | None = None):
    """Mean next-token CE over vocab-sharded logits (see parts)."""
    s, c = cross_entropy_parts(logits_l, labels, ctx, mask,
                               valid_vocab=valid_vocab)
    return s / jnp.maximum(c, 1.0)


def lm_head_loss_chunked(p, name, x, labels, ctx: ShardCtx, *, mask=None,
                         valid_vocab: int | None = None, chunk: int = 512):
    """Fused LM-head + CE, scanned over sequence chunks.

    Never materializes the full [B, S, V/tp] logits — the peak transient is
    [B, chunk, V/tp] (recomputed in backward via remat).  This is the
    difference between fitting and OOM at 200k vocab x 4k seq.
    """
    B, S, d = x.shape
    c = min(chunk, S)
    nc = -(-S // c)
    pad = nc * c - S
    xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    mp = (jnp.pad(mask, ((0, 0), (0, pad)))
          if mask is not None else (lp >= 0))
    xc = xp.reshape(B, nc, c, d).swapaxes(0, 1)
    lc = lp.reshape(B, nc, c).swapaxes(0, 1)
    mc = mp.reshape(B, nc, c).swapaxes(0, 1)

    @jax.checkpoint
    def body(carry, inp):
        s_acc, n_acc = carry
        x_b, l_b, m_b = inp
        logits = linear_col(p, name, x_b)
        s, n = cross_entropy_parts(logits, l_b, ctx, m_b,
                                   valid_vocab=valid_vocab)
        return (s_acc + s, n_acc + n), None

    (s, n), _ = lax.scan(body, (jnp.float32(0), jnp.float32(0)),
                         (xc, lc, mc))
    return s / jnp.maximum(n, 1.0)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_freqs(dim: int, theta: float, yarn) -> tuple:
    """(inverse frequencies [dim/2], cos/sin gain) of YaRN rope scaling,
    DeepSeek-V2's form (``common.Yarn``)."""
    half = dim // 2
    base = theta ** (-np.arange(half, dtype=np.float64) / half)

    def turns_dim(turns):
        return (dim * math.log(yarn.original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(turns_dim(yarn.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(half) - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freqs = base / yarn.factor * ramp + base * (1.0 - ramp)
    gain = (yarn_mscale(yarn.factor, yarn.mscale)
            / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    return freqs.astype(np.float32), gain


def rope(x, positions, theta: float, yarn=None):
    """x [..., S, H, hd] (hd even), positions [..., S] int32; rotate-half
    layout.  ``yarn`` (``common.Yarn``) rescales the frequencies."""
    hd = x.shape[-1]
    half = hd // 2
    if yarn is None:
        freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    else:
        freqs, gain = yarn_freqs(hd, theta, yarn)
    ang = positions[..., None].astype(jnp.float32) * freqs      # [..., S, half]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    if yarn is not None and gain != 1.0:
        cos, sin = cos * gain, sin * gain
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], axis=-1
    ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Flash-style chunked attention (training / prefill)
# ---------------------------------------------------------------------------

NEG = -1e30


def _flash_inner(qf, kc, vc, pos_q, Sk, *, causal, window, chunk,
                 remat_chunks: bool = False):
    """Online-softmax over KV chunks for one q-block.

    qf: [B, Tq, KV, g, hd] (pre-scaled f32); kc/vc: [nC, B, chunk, KV, hd*];
    pos_q: [Tq] (traced).  ``remat_chunks`` recomputes each chunk's scores
    in the backward instead of saving them."""
    B, Tq, KV, g, hd = qf.shape
    hd_v = vc.shape[-1]

    def step(carry, inp):
        m, l, o = carry
        ci, kb, vb = inp
        # named_scope marks the score/softmax chain: a fused attention
        # kernel (repro.kernels.flash) keeps every buffer in here in VMEM.
        # The dry-run's --fused-attn accounting excludes these from the HBM
        # term (hlo_cost exclude_bytes_re="flash_fusable").
        with jax.named_scope("flash_fusable"):
            pos_k = ci * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqkgh,bckh->bqkgc", qf, kb.astype(jnp.float32))
            valid = pos_k[None, :] < Sk
            if causal:
                valid = valid & (pos_k[None, :] <= pos_q[:, None])
            if window > 0:
                valid = valid & (pos_k[None, :] > pos_q[:, None] - window)
            s = jnp.where(valid[None, :, None, None, :], s, NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bqkgc,bckh->bqkgh", p, vb.astype(jnp.float32))
        return (m_new, l_new, o_new), None

    m0 = jnp.full((B, Tq, KV, g), NEG, jnp.float32)
    l0 = jnp.zeros((B, Tq, KV, g), jnp.float32)
    o0 = jnp.zeros((B, Tq, KV, g, hd_v), jnp.float32)
    nC = kc.shape[0]
    if remat_chunks:
        step = jax.checkpoint(step)
    (m, l, o), _ = lax.scan(step, (m0, l0, o0), (jnp.arange(nC), kc, vc))
    return o / jnp.maximum(l, 1e-30)[..., None]


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    chunk: int = 512, q_chunk: int = 1024, q_offset: int = 0,
                    scale: float | None = None):
    """Memory-efficient attention, tiled over BOTH q and kv blocks
    (lax.scan) — the f32 score transient is bounded by
    B * q_chunk * H * chunk * 4 bytes regardless of sequence length.

    q: [B, Sq, H, hd]; k: [B, Sk, KV, hd]; v: [B, Sk, KV, hd_v] with
    H % KV == 0 (GQA).  ``hd_v`` may differ from ``hd`` (MLA).
    ``window > 0`` restricts attention to the last ``window`` positions
    (sliding-window variant enabling long_500k on attention archs).
    Pure jnp — XLA fuses this well on TPU; the running-max/denominator
    recurrence is the standard online-softmax.  ``scale`` defaults to
    1/sqrt(hd).
    """
    B, Sq, H, hd = q.shape
    hd_v = v.shape[-1]
    Sk, KV = k.shape[1], k.shape[2]
    g = H // KV
    if scale is None:
        scale = 1.0 / math.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KV, g, hd)

    nC = (Sk + chunk - 1) // chunk
    pad = nC * chunk - Sk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    kc = kp.reshape(B, nC, chunk, KV, hd).transpose(1, 0, 2, 3, 4)
    vc = vp.reshape(B, nC, chunk, KV, hd_v).transpose(1, 0, 2, 3, 4)

    if Sq <= q_chunk:
        o = _flash_inner(qf, kc, vc, q_offset + jnp.arange(Sq), Sk,
                         causal=causal, window=window, chunk=chunk)
        return o.reshape(B, Sq, H, hd_v).astype(q.dtype)

    nQ = (Sq + q_chunk - 1) // q_chunk
    qpad = nQ * q_chunk - Sq
    qp = jnp.pad(qf, ((0, 0), (0, qpad), (0, 0), (0, 0), (0, 0)))
    qb = qp.reshape(B, nQ, q_chunk, KV, g, hd).transpose(1, 0, 2, 3, 4, 5)

    def q_step(_, inp):
        qi, qblk = inp
        pos_q = qi * q_chunk + jnp.arange(q_chunk)
        # over several q-blocks the saved scores would grow as Sq * Sk:
        # f32[B, Sq, H, Sk] twice, 8.6 GB at 4 rows of 4096 with 16 heads
        o = _flash_inner(qblk, kc, vc, pos_q, Sk, causal=causal,
                         window=window, chunk=chunk, remat_chunks=True)
        return None, o

    _, ob = lax.scan(q_step, None, (jnp.arange(nQ), qb))
    out = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, nQ * q_chunk, H, hd_v)
    return out[:, :Sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention over a sequence-sharded KV cache
# ---------------------------------------------------------------------------

def decode_attention(q, k_loc, v_loc, pos_loc, t, ctx: ShardCtx, *,
                     window: int = 0):
    """One-token attention against a model-axis sequence-sharded cache.

    q: [B, H, hd] (H = local heads if shard_heads else all heads)
    k_loc: [B, Sl, KV, hd]; v_loc: [B, Sl, KV, hd_v] — this rank's
    round-robin slice (hd_v may differ, MLA).
    pos_loc: [Sl] global positions (-1 = never written).
    t: current global position (attend to pos <= t, and > t - window —
    the current token is written to the cache before attending).

    Combines partial softmax stats across the model axis (pmax + psum) —
    the context-parallel decode described in DESIGN.md §5; head-count
    divisibility is irrelevant here.
    """
    B, H, hd = q.shape
    hd_v = v_loc.shape[-1]
    KV = k_loc.shape[2]
    g = H // KV
    scale = 1.0 / math.sqrt(hd)
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, g, hd)
    s = jnp.einsum("bkgh,bskh->bkgs", qf, k_loc.astype(jnp.float32))
    valid = (pos_loc >= 0) & (pos_loc <= t)
    if window > 0:
        valid = valid & (pos_loc > t - window)
    s = jnp.where(valid[None, None, None, :], s, NEG)
    m_l = jnp.max(s, axis=-1)
    m = ctx.pmax_tp(m_l)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[None, None, None, :], p, 0.0)
    l = ctx.psum_tp(jnp.sum(p, axis=-1))
    o = ctx.psum_tp(jnp.einsum("bkgs,bskh->bkgh", p,
                               v_loc.astype(jnp.float32)))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, H, hd_v).astype(q.dtype)


def cache_write(k_loc, v_loc, pos_loc, k_new, v_new, t, ctx: ShardCtx, *,
                window: int = 0):
    """Round-robin write of one token's K/V into the rank owning position t.

    Slot layout: position t lives on rank ``t % tp`` at slot
    ``(t // tp) % Sl`` (ring when a sliding window bounds the cache).
    """
    tp = max(ctx.tp, 1)
    Sl = k_loc.shape[1]
    rank = ctx.tp_rank() if ctx.tp > 1 else 0
    mine = (t % tp) == rank
    slot = (t // tp) % Sl
    k_upd = lax.dynamic_update_slice(
        k_loc, k_new[:, None].astype(k_loc.dtype), (0, slot, 0, 0))
    v_upd = lax.dynamic_update_slice(
        v_loc, v_new[:, None].astype(v_loc.dtype), (0, slot, 0, 0))
    p_upd = lax.dynamic_update_slice(
        pos_loc, jnp.asarray(t, pos_loc.dtype)[None], (slot,))
    k_loc = jnp.where(mine, k_upd, k_loc)
    v_loc = jnp.where(mine, v_upd, v_loc)
    pos_loc = jnp.where(mine, p_upd, pos_loc)
    return k_loc, v_loc, pos_loc


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU), column -> row parallel
# ---------------------------------------------------------------------------

def init_swiglu(b: ParamBuilder, name: str, d: int, d_ff: int, tp: int):
    sub = b.child(name)
    init_linear(sub, "gate", d, d_ff, mode="col", tp=tp)
    init_linear(sub, "up", d, d_ff, mode="col", tp=tp)
    init_linear(sub, "down", d_ff, d, mode="row", tp=tp)


def swiglu(p, name, x, ctx: ShardCtx):
    sub = p[name]
    h = jax.nn.silu(linear_col(sub, "gate", x)) * linear_col(sub, "up", x)
    return linear_row(sub, "down", h, ctx)


def init_gelu_mlp(b: ParamBuilder, name: str, d: int, d_ff: int, tp: int):
    sub = b.child(name)
    init_linear(sub, "up", d, d_ff, mode="col", tp=tp, bias=True)
    init_linear(sub, "down", d_ff, d, mode="row", tp=tp, bias=True)


def gelu_mlp(p, name, x, ctx: ShardCtx):
    sub = p[name]
    return linear_row(sub, "down", jax.nn.gelu(linear_col(sub, "up", x)), ctx)
