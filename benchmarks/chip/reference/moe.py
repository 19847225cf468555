"""Plain float32 reference of a DeepSeek-V2 decoder (arXiv:2405.04434) at
one chip's share of its experts: embedding gather; pre-norm blocks of
multi-head latent attention without query compression (YaRN rope on a
shared rope head, the mscale-squared softmax scale); the leading dense
layers' SwiGLU, then DeepSeekMoE layers (a softmax router over all
experts, greedy top-k, the held experts' SwiGLUs weighted by their gates,
the shared experts' SwiGLU for every token); a final RMSNorm and an
untied LM head, trained on the mean next-token cross-entropy plus each
MoE layer's per-sequence expert-balance loss.

Written from the published description, not from the program: attention
is the full causal softmax over the whole sequence (taken in blocks of
queries, each over every key, so that it fits); each held expert is
computed densely for every token, with a zero gate where the token did
not pick it (no sorting, no grouped matmul).  A token-expert pair routed
to an expert not held adds nothing here, as in the program.

The balance loss is added to the objective and not to the reported loss
(the program reports the cross-entropy).  Its gradient enters through an
identity on the trunk's output whose custom gradient hands the loss its
weight, alpha over the batch's rows (``_balance``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.custom_batching import custom_vmap

from benchmarks.chip.reference import common

# configuration keys (config.json names) and the program's ArchConfig fields
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv",
    "qk_nope_head_dim": "head_dim", "qk_rope_head_dim": "mla_rope_dim",
    "v_head_dim": "mla_v_dim", "kv_lora_rank": "mla_kv_rank",
    "n_routed_experts": "n_experts", "num_experts_per_tok": "top_k",
    "n_shared_experts": "n_shared_experts",
    "first_k_dense_replace": "first_k_dense", "experts_held": "experts_held",
    "norm_topk_prob": "norm_topk", "aux_loss_alpha": "aux_alpha",
    "seq_aux": "seq_aux",
    "vocab_size": "vocab", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps",
}
Q_BLOCK = 512


def init_leaf(path: str, shape, key, c: dict):
    """The shared rule, and for the expert stacks [E, fan_in, fan_out]
    N(0, 1/fan_in); the latent's RMSNorm gain is one."""
    name = path.rsplit("/", 1)[-1]
    if name == "kv_norm":
        return jnp.ones(shape, jnp.float32)
    if name in ("w_gate", "w_up", "w_down"):
        return (jax.random.normal(key, shape, jnp.float32)
                / jnp.sqrt(jnp.float32(shape[-2])))
    w = common.init_linear(path, shape, key, c)
    if w is None:
        raise KeyError(f"no initializer for parameter {path!r}")
    return w


# ---------------------------------------------------------------------------
# YaRN rope (DeepSeek-V2's config: rope_scaling type "yarn")
# ---------------------------------------------------------------------------

def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(c: dict):
    """(inverse frequencies [rope_dim/2], cos/sin gain, softmax scale)."""
    dim, base = c["qk_rope_head_dim"], float(c["rope_theta"])
    rs = c["rope_scaling"]
    factor, orig = float(rs["factor"]), rs["original_max_position_embeddings"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / factor

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                   # 1: original frequency, 0: / factor
    inv = inter * (1.0 - keep) + extra * keep
    gain = _mscale(factor, rs["mscale"]) / _mscale(factor, rs["mscale_all_dim"])
    m = _mscale(factor, rs["mscale_all_dim"])
    scale = (c["qk_nope_head_dim"] + dim) ** -0.5 * m * m
    return jnp.asarray(inv, jnp.float32), gain, scale


# ---------------------------------------------------------------------------
# the balance loss's weight in the objective: alpha over the batch's rows
# ---------------------------------------------------------------------------

@custom_vmap
def _inv_rows(tokens):
    """1 over the rows of the batch the trunk is mapped over."""
    return jnp.float32(1.0)


@_inv_rows.def_vmap
def _inv_rows_vmap(axis_size, in_batched, tokens):
    return jnp.full((axis_size,), 1.0 / axis_size, jnp.float32), True


@jax.custom_vjp
def _balance(x, aux, weight):
    """x unchanged; the objective's gradient with respect to ``aux`` is
    ``weight``."""
    return x


def _balance_fwd(x, aux, weight):
    return x, weight


def _balance_bwd(weight, g):
    return g, weight, jnp.zeros_like(weight)


_balance.defvjp(_balance_fwd, _balance_bwd)


# ---------------------------------------------------------------------------
# the trunk
# ---------------------------------------------------------------------------

def _rope(t, cos, sin):
    """t [S, ..., rd] rotated by the angles of cos/sin [S, rd/2]
    (rotate-half layout)."""
    half = t.shape[-1] // 2
    cs = cos.reshape((t.shape[0],) + (1,) * (t.ndim - 2) + (half,))
    sn = sin.reshape(cs.shape)
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cs - t2 * sn, t2 * cs + t1 * sn], -1)


def attention(h, lp: dict, c: dict, mm):
    """MLA without query compression over one row h [S, d]: q from h, a
    normed latent and one rope head shared by all heads from h, per-head
    no-rope keys and values from the latent; full causal softmax."""
    S = h.shape[0]
    H, nope, rd, vd = (c["num_attention_heads"], c["qk_nope_head_dim"],
                       c["qk_rope_head_dim"], c["v_head_dim"])
    kvr = c["kv_lora_rank"]
    inv, gain, scale = yarn(c)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang) * gain, jnp.sin(ang) * gain
    q = mm("sd,de->se", h, lp["attn/q_w"]).reshape(S, H, nope + rd)
    kv_a = mm("sd,de->se", h, lp["attn/kv_down_w"])
    lat = common.rmsnorm(kv_a[:, :kvr], lp["attn/kv_norm"], c["rms_norm_eps"])
    k_pe = _rope(kv_a[:, kvr:], cos, sin)
    kv = mm("sc,ce->se", lat, lp["attn/kv_up_w"]).reshape(S, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], cos, sin)], -1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (S, H, rd))], -1)
    v = kv[..., nope:]
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(i):                      # queries [i*qb, (i+1)*qb) over every key
        qi = lax.dynamic_slice_in_dim(q, i * qb, qb, 0)
        s = mm("qhd,khd->hqk", qi, k) * scale
        causal = (i * qb + jnp.arange(qb))[:, None] >= jnp.arange(S)[None]
        p = jax.nn.softmax(jnp.where(causal[None], s, common.NEG), axis=-1)
        return mm("hqk,khd->qhd", p, v)

    o = lax.map(block, jnp.arange(S // qb)).reshape(S, H * vd)
    return mm("se,ed->sd", o, lp["attn/o_w"])


def swiglu(h, gate_w, up_w, down_w, mm):
    f = jax.nn.silu(mm("sd,df->sf", h, gate_w)) * mm("sd,df->sf", h, up_w)
    return mm("sf,fd->sd", f, down_w)


def moe_ffn(h, lp: dict, c: dict, mm):
    """(routed + shared experts' output [S, d], the row's balance loss)
    for one row h [S, d]: softmax over all routed experts, greedy top-k,
    gates renormalised only where the configuration says; the held experts
    ``[0, experts_held)`` are each computed for every token and weighted by
    the token's gate for them, zero where the token did not pick it."""
    S = h.shape[0]
    E, K, held = c["n_routed_experts"], c["num_experts_per_tok"], c["experts_held"]
    probs = jax.nn.softmax(mm("sd,de->se", h, lp["ffn/router_w"]), axis=-1)
    top, idx = lax.top_k(probs, K)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    picked = jax.nn.one_hot(idx, E, dtype=jnp.float32)           # [S, K, E]
    gates = jnp.sum(top[..., None] * picked, axis=1)[:, :held]

    def expert(acc, e):
        w = lambda n: lax.dynamic_index_in_dim(lp[n], e, 0, False)  # noqa: E731
        y = swiglu(h, w("ffn/w_gate"), w("ffn/w_up"), w("ffn/w_down"), mm)
        return acc + gates[:, e, None] * y, None

    routed, _ = lax.scan(jax.checkpoint(expert), jnp.zeros_like(h),
                         jnp.arange(held))
    shared = swiglu(h, lp["ffn/shared/gate_w"], lp["ffn/shared/up_w"],
                    lp["ffn/shared/down_w"], mm)
    f = E / (K * S) * jnp.sum(picked, axis=(0, 1))
    return routed + shared, jnp.sum(f * jnp.mean(probs, axis=0))


def layers(P: dict, tokens, c: dict, mm):
    """(hidden states [S, d] after the last layer, the MoE layers'
    balance losses summed) of one row of tokens [S].  Every product is
    taken at ``highest`` precision: on a TPU a float32 matmul otherwise
    rounds its operands."""
    with jax.default_matmul_precision("highest"):
        return _layers(P, tokens, c, mm)


def _layers(P: dict, tokens, c: dict, mm):
    eps = c["rms_norm_eps"]

    def dense_layer(x, lp):
        x = x + attention(common.rmsnorm(x, lp["ln1"], eps), lp, c, mm)
        h = common.rmsnorm(x, lp["ln2"], eps)
        return x + swiglu(h, lp["ffn/gate_w"], lp["ffn/up_w"], lp["ffn/down_w"],
                          mm), None

    def moe_layer(x, lp):
        x = x + attention(common.rmsnorm(x, lp["ln1"], eps), lp, c, mm)
        y, aux = moe_ffn(common.rmsnorm(x, lp["ln2"], eps), lp, c, mm)
        return x + y, aux

    x = P["embed/table"][tokens]
    dense = common.layer_params(P, "dense_layers/")
    if dense:
        x, _ = lax.scan(jax.checkpoint(dense_layer), x, dense)
    x, aux = lax.scan(jax.checkpoint(moe_layer), x, common.layer_params(P))
    return x, jnp.sum(aux)


def trunk(P: dict, tokens, c: dict, mm):
    """Final normed hidden states [S, d] of one row of tokens [S]; the
    row's balance loss enters the gradient through ``_balance``."""
    x, aux = layers(P, tokens, c, mm)
    x = _balance(x, aux, c["aux_loss_alpha"] * _inv_rows(tokens))
    return common.rmsnorm(x, P["ln_f"], c["rms_norm_eps"])


def balance_loss(P: dict, tokens, c: dict, mm):
    """The MoE layers' per-sequence balance losses, summed over layers and
    averaged over the rows of tokens [B, S] (alpha not applied)."""
    return jnp.mean(jax.vmap(lambda t: layers(P, t, c, mm)[1])(tokens))
