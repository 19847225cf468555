"""Attention modules: GQA (RoPE, optional QKV bias), cross-attention, MLA.

Sharding: q-heads column-parallel over ``model`` when divisible
(``ctx.shard_heads``), KV projections always replicated (DESIGN.md §5);
decode uses the sequence-sharded cache from ``layers``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.models.common import (ArchConfig, ParamBuilder, ShardCtx,
                                 zero_cols_from, zero_rows_from)
from repro.models import layers as L


# ---------------------------------------------------------------------------
# GQA self-attention
# ---------------------------------------------------------------------------

def _H(cfg: ArchConfig, ctx: ShardCtx) -> int:
    """Effective (possibly padded) q-head count."""
    return ctx.h_pad or cfg.n_heads


def _zero_pad_cols(sub, name: str, start_col: int):
    """Zero the padded-head columns so the init function IS the spec arch."""
    zero_cols_from(sub, f"{name}_w", start_col)
    zero_cols_from(sub, f"{name}_b", start_col)


def _zero_pad_rows(sub, name: str, start_row: int):
    zero_rows_from(sub, f"{name}_w", start_row)


def init_gqa(b: ParamBuilder, name: str, cfg: ArchConfig, ctx: ShardCtx,
             *, cross: bool = False):
    sub = b.child(name)
    d, H, hd, kv = cfg.d_model, _H(cfg, ctx), cfg.hd, cfg.n_kv
    q_mode = "col" if ctx.shard_heads else "rep"
    o_mode = "row" if ctx.shard_heads else "rep"
    L.init_linear(sub, "q", d, H * hd, mode=q_mode, tp=ctx.tp, bias=cfg.qkv_bias)
    L.init_linear(sub, "k", d, kv * hd, mode="rep", tp=ctx.tp, bias=cfg.qkv_bias)
    L.init_linear(sub, "v", d, kv * hd, mode="rep", tp=ctx.tp, bias=cfg.qkv_bias)
    L.init_linear(sub, "o", H * hd, d, mode=o_mode, tp=ctx.tp)
    if ctx.h_pad:
        _zero_pad_cols(sub, "q", cfg.n_heads * hd)
        _zero_pad_rows(sub, "o", cfg.n_heads * hd)


def _heads_local(cfg: ArchConfig, ctx: ShardCtx) -> int:
    H = _H(cfg, ctx)
    return H // ctx.tp if ctx.shard_heads else H


def _kv_slice(k, v, cfg: ArchConfig, ctx: ShardCtx, axis: int):
    """Slice the KV heads this rank's q-head shard actually uses.

    KV projections are replicated (DESIGN.md §5), so every rank computes all
    KV heads; with q-heads sharded, rank r's local q heads [r*Hl, (r+1)*Hl)
    attend to kv heads [r*Hl//g, ...) where g = H // KV.  Requires Hl % g == 0
    or g % Hl == 0 — true for the whole assigned zoo at tp in {1..16}.
    """
    if not ctx.shard_heads or ctx.tp == 1:
        return k, v
    H, KV = _H(cfg, ctx), cfg.n_kv
    Hl = H // ctx.tp
    g = H // KV
    if Hl >= g:
        assert Hl % g == 0, (Hl, g)
        count = Hl // g
    else:
        assert g % Hl == 0, (Hl, g)
        count = 1
    r = ctx.tp_rank()
    start = (r * Hl) // g
    k = jax.lax.dynamic_slice_in_dim(k, start, count, axis)
    v = jax.lax.dynamic_slice_in_dim(v, start, count, axis)
    return k, v


def gqa_train(p, name, x, cfg: ArchConfig, ctx: ShardCtx, *,
              positions=None, window: int = 0, causal: bool = True,
              kv_src=None, use_rope: bool = True):
    """Training / prefill attention. ``kv_src`` (e.g. encoder output) makes
    this cross-attention (no rope on kv, no causal mask)."""
    sub = p[name]
    B, S, _ = x.shape
    Hl, hd, kv = _heads_local(cfg, ctx), cfg.hd, cfg.n_kv
    src = x if kv_src is None else kv_src
    q = L.linear_col(sub, "q", x).reshape(B, S, Hl, hd)
    k = L.linear_rep(sub, "k", src).reshape(B, src.shape[1], kv, hd)
    v = L.linear_rep(sub, "v", src).reshape(B, src.shape[1], kv, hd)
    if use_rope and kv_src is None:
        pos = positions if positions is not None else jnp.arange(S)
        q = L.rope(q, pos, cfg.rope_theta)
        k = L.rope(k, pos, cfg.rope_theta)
    k, v = _kv_slice(k, v, cfg, ctx, axis=2)
    out = L.flash_attention(q, k, v, causal=causal and kv_src is None,
                            window=window)
    out = out.reshape(B, S, Hl * hd)
    return (L.linear_row(sub, "o", out, ctx) if ctx.shard_heads
            else L.linear_rep(sub, "o", out))


def gqa_make_cache(cfg: ArchConfig, ctx: ShardCtx, batch: int, seq: int,
                   dtype=jnp.bfloat16):
    """Per-layer cache pytree (sequence-sharded over model: local Sl)."""
    tp = ctx.tp if ctx.decode_seq_shard else 1
    sl = max(1, -(-seq // tp))
    kv, hd = cfg.n_kv, cfg.hd
    return {
        "k": jnp.zeros((batch, sl, kv, hd), dtype),
        "v": jnp.zeros((batch, sl, kv, hd), dtype),
        "pos": jnp.full((sl,), -1, jnp.int32),
    }


def gqa_prefill_cache(p, name, x, cfg: ArchConfig, ctx: ShardCtx):
    """Compute K/V for a full prompt and return the seq-sharded cache slice
    (round-robin: rank r owns positions r, r+tp, ...)."""
    sub = p[name]
    B, S, _ = x.shape
    kv, hd = cfg.n_kv, cfg.hd
    k = L.linear_rep(sub, "k", x).reshape(B, S, kv, hd)
    v = L.linear_rep(sub, "v", x).reshape(B, S, kv, hd)
    k = L.rope(k, jnp.arange(S), cfg.rope_theta)
    tp = ctx.tp if ctx.decode_seq_shard else 1
    r = ctx.tp_rank() if (ctx.tp > 1 and ctx.decode_seq_shard) else 0
    sl = -(-S // tp)
    slots = jnp.arange(sl) * tp + r          # my global positions
    safe = jnp.clip(slots, 0, S - 1)
    ok = slots < S
    return {
        "k": jnp.where(ok[None, :, None, None], k[:, safe], 0),
        "v": jnp.where(ok[None, :, None, None], v[:, safe], 0),
        "pos": jnp.where(ok, slots, -1).astype(jnp.int32),
    }


def gqa_decode(p, name, x, cache, t, cfg: ArchConfig, ctx: ShardCtx, *,
               window: int = 0):
    """One-token decode. x: [B, d]; t: current global position (scalar)."""
    sub = p[name]
    B = x.shape[0]
    Hl, hd, kv = _heads_local(cfg, ctx), cfg.hd, cfg.n_kv
    q = L.linear_col(sub, "q", x).reshape(B, Hl, hd)
    k = L.linear_rep(sub, "k", x).reshape(B, kv, hd)
    v = L.linear_rep(sub, "v", x).reshape(B, kv, hd)
    tpos = jnp.full((1,), t, jnp.int32)
    q = L.rope(q[:, None], tpos, cfg.rope_theta)[:, 0]
    k = L.rope(k[:, None], tpos, cfg.rope_theta)[:, 0]
    kc, vc, pc = L.cache_write(cache["k"], cache["v"], cache["pos"],
                               k, v, t, ctx)
    ku, vu = _kv_slice(kc, vc, cfg, ctx, axis=2)
    out = L.decode_attention(q, ku, vu, pc, t, ctx, window=window)
    out = out.reshape(B, Hl * hd)
    y = (L.linear_row(sub, "o", out, ctx) if ctx.shard_heads
         else L.linear_rep(sub, "o", out))
    return y, {"k": kc, "v": vc, "pos": pc}


def gqa_cross_decode(p, name, x, cross_cache, cfg: ArchConfig, ctx: ShardCtx):
    """Cross-attention during decode: KV precomputed from encoder output
    (replicated — encoder length is short, 1500 frames)."""
    sub = p[name]
    B = x.shape[0]
    Hl, hd = _heads_local(cfg, ctx), cfg.hd
    q = L.linear_col(sub, "q", x).reshape(B, 1, Hl, hd)
    ku, vu = _kv_slice(cross_cache["k"], cross_cache["v"], cfg, ctx, axis=2)
    out = L.flash_attention(q, ku, vu, causal=False)
    out = out.reshape(B, Hl * hd)
    return (L.linear_row(sub, "o", out, ctx) if ctx.shard_heads
            else L.linear_rep(sub, "o", out))


def gqa_make_cross_cache(p, name, enc_out, cfg: ArchConfig, ctx: ShardCtx):
    sub = p[name]
    B, S, _ = enc_out.shape
    kv, hd = cfg.n_kv, cfg.hd
    k = L.linear_rep(sub, "k", enc_out).reshape(B, S, kv, hd)
    v = L.linear_rep(sub, "v", enc_out).reshape(B, S, kv, hd)
    return {"k": k, "v": v}


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention — minicpm3 with q compression,
# deepseek-v2 without)
# ---------------------------------------------------------------------------

def init_mla(b: ParamBuilder, name: str, cfg: ArchConfig, ctx: ShardCtx):
    sub = b.child(name)
    d, H = cfg.d_model, _H(cfg, ctx)
    hd_n, rd, vd = cfg.hd, cfg.mla_rope_dim, cfg.mla_v_dim
    qr, kvr = cfg.mla_q_rank, cfg.mla_kv_rank
    up_mode = "col" if ctx.shard_heads else "rep"
    o_mode = "row" if ctx.shard_heads else "rep"
    if qr:
        L.init_linear(sub, "q_down", d, qr, mode="rep", tp=ctx.tp)
        L.init_linear(sub, "q_up", qr, H * (hd_n + rd), mode=up_mode, tp=ctx.tp)
        L.init_rmsnorm(sub, "q_norm", qr)
    else:
        L.init_linear(sub, "q", d, H * (hd_n + rd), mode=up_mode, tp=ctx.tp)
    L.init_linear(sub, "kv_down", d, kvr + rd, mode="rep", tp=ctx.tp)
    L.init_linear(sub, "kv_up", kvr, H * (hd_n + vd), mode=up_mode, tp=ctx.tp)
    L.init_linear(sub, "o", H * vd, d, mode=o_mode, tp=ctx.tp)
    L.init_rmsnorm(sub, "kv_norm", kvr)
    if ctx.h_pad:
        _zero_pad_cols(sub, "q_up" if qr else "q", cfg.n_heads * (hd_n + rd))
        _zero_pad_cols(sub, "kv_up", cfg.n_heads * (hd_n + vd))
        _zero_pad_rows(sub, "o", cfg.n_heads * vd)


def mla_scale(cfg: ArchConfig) -> float:
    """Softmax scale: 1/sqrt(q dim), times YaRN's mscale squared."""
    scale = 1.0 / math.sqrt(cfg.hd + cfg.mla_rope_dim)
    y = cfg.yarn
    if y is not None and y.mscale_all_dim:
        scale *= L.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_qkv(sub, x, cfg: ArchConfig, ctx: ShardCtx, positions):
    """Shared q / latent computation. Returns q [B,S,Hl,hd+rd],
    c [B,S,kvr], k_rope [B,S,rd]."""
    B, S, _ = x.shape
    Hl = _heads_local(cfg, ctx)
    hd_n, rd = cfg.hd, cfg.mla_rope_dim
    eps = cfg.norm_eps
    if cfg.mla_q_rank:
        cq = L.rmsnorm(sub["q_norm"], L.linear_rep(sub, "q_down", x), eps)
        q = L.linear_col(sub, "q_up", cq)
    else:
        q = L.linear_col(sub, "q", x)
    q = q.reshape(B, S, Hl, hd_n + rd)
    kv_c = L.linear_rep(sub, "kv_down", x)
    c = L.rmsnorm(sub["kv_norm"], kv_c[..., :cfg.mla_kv_rank], eps)
    k_rope = kv_c[..., cfg.mla_kv_rank:]
    q_nope, q_rope = q[..., :hd_n], q[..., hd_n:]
    q_rope = L.rope(q_rope, positions, cfg.rope_theta, yarn=cfg.yarn)
    k_rope = L.rope(k_rope[:, :, None, :], positions, cfg.rope_theta,
                    yarn=cfg.yarn)[:, :, 0]
    return jnp.concatenate([q_nope, q_rope], -1), c, k_rope


def mla_train(p, name, x, cfg: ArchConfig, ctx: ShardCtx, *,
              positions=None, window: int = 0):
    sub = p[name]
    B, S, _ = x.shape
    Hl = _heads_local(cfg, ctx)
    hd_n, rd, vd = cfg.hd, cfg.mla_rope_dim, cfg.mla_v_dim
    pos = positions if positions is not None else jnp.arange(S)
    q, c, k_rope = _mla_qkv(sub, x, cfg, ctx, pos)
    kv = L.linear_col(sub, "kv_up", c).reshape(B, S, Hl, hd_n + vd)
    k = jnp.concatenate(
        [kv[..., :hd_n], jnp.broadcast_to(k_rope[:, :, None, :],
                                          (B, S, Hl, rd))], -1)
    v = kv[..., hd_n:]
    out = L.flash_attention(q, k, v, causal=True, window=window,
                            scale=mla_scale(cfg))
    out = out.reshape(B, S, Hl * vd)
    return (L.linear_row(sub, "o", out, ctx) if ctx.shard_heads
            else L.linear_rep(sub, "o", out))


def mla_make_cache(cfg: ArchConfig, ctx: ShardCtx, batch: int, seq: int,
                   dtype=jnp.bfloat16):
    """Latent cache: c [B,Sl,kvr] + k_rope [B,Sl,rd] — the MLA memory win
    (no per-head K/V stored)."""
    tp = ctx.tp if ctx.decode_seq_shard else 1
    sl = max(1, -(-seq // tp))
    return {
        "c": jnp.zeros((batch, sl, cfg.mla_kv_rank), dtype),
        "kr": jnp.zeros((batch, sl, cfg.mla_rope_dim), dtype),
        "pos": jnp.full((sl,), -1, jnp.int32),
    }


def mla_decode(p, name, x, cache, t, cfg: ArchConfig, ctx: ShardCtx, *,
               window: int = 0):
    """Absorbed-matrices MLA decode against the latent cache."""
    sub = p[name]
    B = x.shape[0]
    Hl = _heads_local(cfg, ctx)
    hd_n, rd, vd, kvr = cfg.hd, cfg.mla_rope_dim, cfg.mla_v_dim, cfg.mla_kv_rank
    tpos = jnp.full((1,), t, jnp.int32)
    q, c_new, kr_new = _mla_qkv(sub, x[:, None], cfg, ctx, tpos)
    q, c_new, kr_new = q[:, 0], c_new[:, 0], kr_new[:, 0]
    q_nope, q_rope = q[..., :hd_n], q[..., hd_n:]
    # absorb W_uk: q' = q_nope @ W_uk  -> score against latent c directly
    w_up = sub["kv_up_w"].reshape(kvr, Hl, hd_n + vd)
    w_uk, w_uv = w_up[..., :hd_n], w_up[..., hd_n:]
    q_lat = jnp.einsum("bhd,khd->bhk", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))          # [B, Hl, kvr]
    # cache write (single "kv head" of latent + rope)
    cc, krc, pc = L.cache_write(
        cache["c"][:, :, None, :], cache["kr"][:, :, None, :], cache["pos"],
        c_new[:, None, :], kr_new[:, None, :], t, ctx)
    cc, krc = cc[:, :, 0, :], krc[:, :, 0, :]
    scale = mla_scale(cfg)
    s = (jnp.einsum("bhk,bsk->bhs", q_lat, cc.astype(jnp.float32))
         + jnp.einsum("bhr,bsr->bhs", q_rope.astype(jnp.float32),
                      krc.astype(jnp.float32))) * scale
    valid = (pc >= 0) & (pc <= t)
    if window > 0:
        valid = valid & (pc > t - window)
    s = jnp.where(valid[None, None, :], s, L.NEG)
    m = ctx.pmax_tp(jnp.max(s, axis=-1))
    pw = jnp.where(valid[None, None, :], jnp.exp(s - m[..., None]), 0.0)
    l = ctx.psum_tp(jnp.sum(pw, axis=-1))
    ctx_c = ctx.psum_tp(jnp.einsum("bhs,bsk->bhk", pw,
                                   cc.astype(jnp.float32)))
    ctx_c = ctx_c / jnp.maximum(l, 1e-30)[..., None]
    out = jnp.einsum("bhk,khv->bhv", ctx_c,
                     w_uv.astype(jnp.float32)).astype(x.dtype)
    out = out.reshape(B, Hl * vd)
    y = (L.linear_row(sub, "o", out, ctx) if ctx.shard_heads
         else L.linear_rep(sub, "o", out))
    return y, {"c": cc, "kr": krc, "pos": pc}
