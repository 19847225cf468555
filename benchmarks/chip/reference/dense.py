"""Plain float32 reference of a dense decoder (qwen2): embedding gather,
pre-norm blocks of GQA attention with QKV bias and rotary embeddings
(rotate-half form), SwiGLU MLP, a final RMSNorm and an untied LM head,
trained on the mean next-token cross-entropy.

Full causal softmax attention over the whole sequence, no cache, no
batching beyond one row per call.  Parameters arrive as a flat
``{"a/b/c": array}`` dict in float32; stacked leaves under ``layers/``
carry a leading layer axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.chip.reference import common

# configuration keys (config.json names) and the program's ArchConfig fields
ARCH_FIELDS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "intermediate_size": "d_ff", "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv", "head_dim": "head_dim",
    "vocab_size": "vocab", "rope_theta": "rope_theta",
    "attention_bias": "qkv_bias",
}


def init_leaf(path: str, shape, key, c: dict):
    w = common.init_linear(path, shape, key, c)
    if w is None:
        raise KeyError(f"no initializer for parameter {path!r}")
    return w


def trunk(P: dict, tokens, c: dict, mm):
    """Final normed hidden states [S, d] of one row of tokens [S]."""
    S = tokens.shape[0]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    half = hd // 2
    inv = c["rope_theta"] ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(t):
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        h = common.rmsnorm(x, lp["ln1"], eps)
        q = (mm("sd,de->se", h, lp["attn/q_w"]) + lp["attn/q_b"]).reshape(S, H, hd)
        k = (mm("sd,de->se", h, lp["attn/k_w"]) + lp["attn/k_b"]).reshape(S, KV, hd)
        v = (mm("sd,de->se", h, lp["attn/v_w"]) + lp["attn/v_b"]).reshape(S, KV, hd)
        q, k = rope(q), rope(k)
        k = jnp.repeat(k, H // KV, axis=1)
        v = jnp.repeat(v, H // KV, axis=1)
        s = mm("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
        p = jax.nn.softmax(jnp.where(causal[None], s, common.NEG), axis=-1)
        o = mm("hqk,khd->qhd", p, v).reshape(S, H * hd)
        x = x + mm("se,ed->sd", o, lp["attn/o_w"])
        h = common.rmsnorm(x, lp["ln2"], eps)
        f = (jax.nn.silu(mm("sd,df->sf", h, lp["ffn/gate_w"]))
             * mm("sd,df->sf", h, lp["ffn/up_w"]))
        return x + mm("sf,fd->sd", f, lp["ffn/down_w"]), None

    x = P["embed/table"][tokens]
    x, _ = lax.scan(jax.checkpoint(layer), x, common.layer_params(P))
    return common.rmsnorm(x, P["ln_f"], eps)
