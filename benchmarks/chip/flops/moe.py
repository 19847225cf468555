"""Model FLOPs per trained token of a DeepSeek-V2 decoder at a share of
its experts.

forward = 2 x (matmul parameters a token passes through: each layer's MLA
projections (q, the latent and rope head, the latent's up-projection to
keys and values, the output); the leading dense layers' SwiGLU; each MoE
layer's router, shared experts, and routed experts at the expected
top_k x held / n_experts token-expert pairs a token computes here; the LM
head over the vocabulary as run; the input embedding table, a gather,
excluded) + per attention layer 2 x S x n_heads x (qk dim + v dim) for the
score and value products over the whole sequence, with no causal halving
(dense.py's convention).  Per token = 3 x forward.  Recomputation is not
counted.

``pair_flops`` is one token-expert pair's forward through an expert's
three projections; the held experts' grouped matmuls do 4 x that per pair
in a step (forward, recomputed forward, and twice in backward), which the
``moe.experts_roofline`` metric counts.
"""

PAIR_PASSES = 4     # forward + recomputed forward + 2 x backward


def _mla_params(c: dict) -> int:
    d, H = c["hidden_size"], c["num_attention_heads"]
    nope, rd, vd, kvr = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                         c["v_head_dim"], c["kv_lora_rank"])
    return d * H * (nope + rd) + d * (kvr + rd) + kvr * H * (nope + vd) + H * vd * d


def pair_flops(c: dict) -> float:
    return 2.0 * 3 * c["hidden_size"] * c["moe_intermediate_size"]


def forward_flops(c: dict, seq_len: int) -> float:
    d, L, V = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    k = c["first_k_dense_replace"]
    E, K, held = c["n_routed_experts"], c["num_experts_per_tok"], c["experts_held"]
    f = c["moe_intermediate_size"]
    dense_ffn = 3 * d * c["intermediate_size"]
    moe_ffn = d * E + c["n_shared_experts"] * 3 * d * f + K * held / E * 3 * d * f
    matmul = L * _mla_params(c) + k * dense_ffn + (L - k) * moe_ffn + d * V
    attn = 2.0 * seq_len * c["num_attention_heads"] * (
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])
    return 2.0 * matmul + L * attn


def flops_per_token(c: dict, seq_len: int) -> float:
    return 3.0 * forward_flops(c, seq_len)
