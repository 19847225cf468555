"""Mixture-of-Experts FFN: a router over all experts, the experts held
here, and shared experts every token passes through.

The layer holds routed experts ``[0, cfg.held)`` (one chip's share of an
expert-parallel group; all of them by default), sharded over ``model``:
rank r holds ``[r*El, (r+1)*El)``.  Routing is replicated over ``model``
and computed in float32: a softmax over all ``n_experts``, the top-k,
the gates renormalised or not (``cfg.norm_topk``).  The token-expert
pairs whose expert this rank holds are sorted by expert and go through
one grouped matmul per projection (Pallas ``megablox.gmm``), dropless:
the sorted buffer has a row for every pair, whatever the routing.  Pairs
routed to an expert not held here add nothing (DESIGN.md §16).  The
results are gathered back to their tokens, weighted by the gate, summed
over ``model`` by the row-parallel psum, and the shared experts' SwiGLU
is added.

Stats: the expert-balance loss over all ``n_experts``, per sequence
(DeepSeek-V2, arXiv:2405.04434 §2.2.3) or Switch-style over the batch
(``cfg.seq_aux``), the pairs computed here (``moe/held_pairs``), and the
router skew.  ``moe_a2a`` keeps its
capacity-bounded exchange (static all-to-all buffers).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.sharding import PartitionSpec as P

from repro.kernels.ops import default_interpret
from repro.models import layers as L
from repro.models.common import ArchConfig, ParamBuilder, ShardCtx

ROUTE = "moe.route"
EXPERTS = "moe.experts"
# megablox tile (rows, contraction, columns); 512 measured 1.9x faster
# than lax.ragged_dot on a v5e at the deepseek-v2-lite cell's shapes
# (PERF.md §6).
TILE = 512


def init_moe(b: ParamBuilder, name: str, cfg: ArchConfig, ctx: ShardCtx):
    sub = b.child(name)
    d, f, E, Eh = cfg.d_model, cfg.expert_ff, cfg.n_experts, cfg.held
    assert Eh % ctx.tp == 0, (Eh, ctx.tp)
    sub.dense("router_w", (d, E), P(None, None), scale=0.02)
    # held expert weights: [Eh, ...] sharded over model on the expert dim
    scale_in = 1.0 / math.sqrt(d)
    scale_out = 1.0 / math.sqrt(f)
    sub.dense("w_gate", (Eh, d, f), P("model", None, None), scale=scale_in)
    sub.dense("w_up", (Eh, d, f), P("model", None, None), scale=scale_in)
    sub.dense("w_down", (Eh, f, d), P("model", None, None), scale=scale_out)
    if cfg.n_shared_experts:
        L.init_swiglu(sub, "shared", d, cfg.n_shared_experts * f, ctx.tp)


def moe_ffn(p, name, x, cfg: ArchConfig, ctx: ShardCtx):
    """x [B, S, d] -> (y [B, S, d], stats).  The token-sharded all-to-all
    dispatch (``ctx.moe_a2a``) or the replicated dropless one."""
    tokens = x.shape[0] * x.shape[1]
    if (getattr(ctx, "moe_a2a", False) and ctx.tp > 1
            and tokens % ctx.tp == 0):
        y, stats = moe_ffn_a2a(p, name, x, cfg, ctx)
    else:
        # decode steps (T < tp) and non-divisible token counts use the
        # replicated dispatch
        y, stats = moe_ffn_dropless(p, name, x, cfg, ctx)
    if cfg.n_shared_experts:
        y = y + L.swiglu(p[name], "shared", x, ctx)
    return y, stats


def _route(xf, router_w, cfg: ArchConfig):
    """float32 router: (probs [T, E], gates [T, K], experts [T, K])."""
    logits = jnp.matmul(xf.astype(jnp.float32), router_w.astype(jnp.float32),
                        precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, eidx = lax.top_k(probs, cfg.top_k)
    if cfg.norm_topk:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return probs, gate, eidx


def balance_loss(probs, eidx, rows: int, cfg: ArchConfig, ctx=None):
    """Expert-balance loss ``sum_i f_i P_i``, with ``f_i = E/(K S) count_i``
    and ``P_i`` the mean router probability of expert i over S tokens.
    With ``cfg.seq_aux`` per sequence and averaged over the ``rows``
    sequences (DeepSeek-V2); otherwise over the whole batch at once, the
    Switch form ``E sum_i (count_i / (K T)) mean_t p_i``.  ``probs`` [T, E]
    and ``eidx`` [T, K] hold ``rows`` whole sequences, or (with ``ctx``)
    this model rank's slice of them, summed over ``model``.  The sums
    over a sequence are matmuls with a one-hot of each token's row: as
    scatter-adds they serialise on their duplicate indices.  Returns the
    loss and the expert counts per sequence [rows or 1, E]."""
    rows = rows if cfg.seq_aux else 1
    T, E = probs.shape
    K = eidx.shape[1]
    S = T // rows if ctx is None else T * ctx.tp // rows
    first = 0 if ctx is None else ctx.tp_rank() * T
    row = jax.nn.one_hot((first + jnp.arange(T)) // S, rows, dtype=jnp.float32)
    picked = jnp.sum(jax.nn.one_hot(eidx, E, dtype=jnp.float32), axis=1)
    counts, psum = (jnp.einsum("tr,te->re", row, v,
                               precision=lax.Precision.HIGHEST)
                    for v in (picked, probs))
    if ctx is not None:
        counts, psum = lax.psum((counts, psum), ctx.tp_axis)
    per_seq = jnp.sum(counts * psum, axis=-1) * E / (K * S * S)
    return jnp.mean(per_seq), counts


@jax.custom_vjp
def _take_rows(x, idx, inv):
    """``x[idx]`` for a permutation ``idx`` of x's rows, ``inv`` its
    inverse.  The transpose is the inverse gather: autodiff's scatter-add
    would not know the indices are distinct."""
    return x[idx]


def _take_rows_fwd(x, idx, inv):
    return x[idx], (idx, inv)


def _take_rows_bwd(res, g):
    idx, inv = res
    return g[inv], None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _gmm(x, w, sizes):
    """Grouped matmul over the sorted rows: ``x[rows of group g] @ w[g]``
    for the held groups; ``sizes`` has one more entry, the rows past them,
    which are not computed and come back as zeros (in the backward too)."""
    m, k = x.shape
    tiling = (math.gcd(m, TILE), min(TILE, k), min(TILE, w.shape[2]))
    return megablox.gmm(x, w, sizes, x.dtype, tiling, None, None, False,
                        default_interpret())


def moe_ffn_dropless(p, name, x, cfg: ArchConfig, ctx: ShardCtx):
    """x [B, S, d] -> (routed experts' y [B, S, d], stats)."""
    sub = p[name]
    Bt, S, d = x.shape
    K = cfg.top_k
    El = cfg.held // ctx.tp
    T = Bt * S
    xf = x.reshape(T, d)

    with jax.named_scope(ROUTE):
        probs, gate, eidx = _route(xf, sub["router_w"], cfg)
        first = ctx.tp_rank() * El if ctx.tp > 1 else 0
        local = eidx.reshape(T * K) - first
        # pairs not held form a last group, which is not computed
        group = jnp.where((local >= 0) & (local < El), local, El)
        order = jnp.argsort(group, stable=True)     # sorted row -> pair
        pos = jnp.zeros(T * K, jnp.int32).at[order].set(   # pair -> row
            jnp.arange(T * K, dtype=jnp.int32), unique_indices=True)
        sizes = jnp.sum(jax.nn.one_hot(group, El + 1, dtype=jnp.int32), axis=0)
        xin = _take_rows(jnp.repeat(xf, K, axis=0), order, pos)

    with jax.named_scope(EXPERTS):
        h = (jax.nn.silu(_gmm(xin, sub["w_gate"], sizes))
             * _gmm(xin, sub["w_up"], sizes))
        y = _gmm(h, sub["w_down"], sizes)

    with jax.named_scope(ROUTE):
        # rows past the held groups come back from the gmm as zeros
        y = _take_rows(y, pos, order).reshape(T, K, d)
        y = jnp.sum(y.astype(jnp.float32) * gate[..., None], axis=1)
        out = ctx.psum_tp(y)
        aux, counts = balance_loss(probs, eidx, Bt, cfg)
        load = jnp.sum(counts, axis=0)
        n_held = T * K - sizes[El]
        stats = {"moe/aux_loss": aux,
                 "moe/held_pairs": ctx.psum_tp(n_held).astype(jnp.float32),
                 # router skew ≙ Def. 5: max expert load / mean load
                 "moe/skew": jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9)}
    return out.reshape(Bt, S, d).astype(x.dtype), stats


def moe_ffn_a2a(p, name, x, cfg: ArchConfig, ctx: ShardCtx):
    """§Perf token-sharded expert dispatch (beyond-paper optimization).

    Baseline replicates routing+dispatch over the model axis and combines
    expert outputs with a full-activation psum (2(g-1)/g * T*d on the wire
    per layer).  Here each model rank routes only its T/tp token slice and
    ships tokens to expert owners with two all-to-alls, then all-gathers
    the sharded output: wire ~ (2*K*cf/tp + 1) * (g-1)/g * T*d — ~35% less
    at phi3.5's K=2, and 16x less routing/dispatch compute and buffers.
    Equivalent to the dropless dispatch while no expert's queue passes
    its capacity (counted per token slice); pairs above it are dropped.
    """
    sub = p[name]
    Bt, S, d = x.shape
    E, K, tp = cfg.n_experts, cfg.top_k, ctx.tp
    El = E // tp
    T = Bt * S
    assert T % tp == 0
    Tl = T // tp
    r = ctx.tp_rank()
    xf = x.reshape(T, d)
    xl = jax.lax.dynamic_slice(xf, (r * Tl, jnp.int32(0)), (Tl, d))

    # ---- local routing on the token slice -----------------------------------
    probs, gate, eidx = _route(xl, sub["router_w"], cfg)  # [Tl, K]

    # ---- local dispatch into per-(expert) queues -----------------------------
    cap = max(1, int(math.ceil(Tl * K / E * cfg.capacity_factor)))
    flat_e = eidx.reshape(Tl * K)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    pos_in_e = jnp.arange(Tl * K) - jnp.searchsorted(sorted_e, sorted_e,
                                                     side="left")
    rank_in_e = jnp.zeros(Tl * K, jnp.int32).at[order].set(
        pos_in_e.astype(jnp.int32))
    keep = rank_in_e < cap
    slot = flat_e * cap + rank_in_e
    tok_of = jnp.repeat(jnp.arange(Tl, dtype=jnp.int32), K)
    buf_tok = jnp.full((E * cap,), Tl, jnp.int32).at[
        jnp.where(keep, slot, E * cap)].set(tok_of, mode="drop")
    buf_gate = jnp.zeros((E * cap,), jnp.float32).at[
        jnp.where(keep, slot, E * cap)].set(gate.reshape(-1), mode="drop")
    safe_tok = jnp.where(buf_tok == Tl, 0, buf_tok)
    xin = xl[safe_tok]
    xin = jnp.where((buf_tok == Tl)[:, None], 0, xin)     # [E*cap, d]

    # ---- ship tokens to expert owners (all-to-all over model) ---------------
    send = xin.reshape(tp, El * cap, d)
    recv = lax.all_to_all(send, ctx.tp_axis, split_axis=0, concat_axis=0)
    g_send = buf_gate.reshape(tp, El * cap)
    g_recv = lax.all_to_all(g_send, ctx.tp_axis, split_axis=0, concat_axis=0)

    # ---- expert FFN on my El experts, tokens from every source rank ---------
    xin_e = recv.reshape(tp, El, cap, d).transpose(1, 0, 2, 3) \
                .reshape(El, tp * cap, d)

    def expert(wg, wu, wd, xi):
        h = jax.nn.silu(xi @ wg) * (xi @ wu)
        return h @ wd

    yex = jax.vmap(expert)(sub["w_gate"], sub["w_up"], sub["w_down"], xin_e)
    g_e = g_recv.reshape(tp, El, cap).transpose(1, 0, 2).reshape(El, tp * cap)
    yex = yex * g_e[..., None].astype(yex.dtype)

    # ---- ship results back, combine into the local token slice ---------------
    back = yex.reshape(El, tp, cap, d).transpose(1, 0, 2, 3) \
              .reshape(tp, El * cap, d)
    got = lax.all_to_all(back, ctx.tp_axis, split_axis=0, concat_axis=0)
    out_l = jnp.zeros((Tl, d), got.dtype)
    out_l = out_l.at[buf_tok].add(got.reshape(E * cap, d), mode="drop")

    # ---- restore replication --------------------------------------------------
    out = lax.all_gather(out_l, ctx.tp_axis, tiled=True)  # [T, d]

    aux, counts = balance_loss(probs, eidx, Bt, cfg, ctx)
    load = jnp.sum(counts, axis=0)
    kept = lax.psum(jnp.sum(keep.astype(jnp.float32)), ctx.tp_axis)
    stats = {"moe/aux_loss": aux, "moe/held_pairs": kept,
             "moe/dropped": 1.0 - kept / (T * K),
             "moe/skew": jnp.max(load) / jnp.maximum(jnp.mean(load), 1e-9)}
    return out.reshape(Bt, S, d).astype(x.dtype), stats
