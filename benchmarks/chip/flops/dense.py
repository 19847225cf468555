"""Model FLOPs per trained token of a dense decoder.

forward = 2 x (matmul parameters, the LM head included, the input
embedding table excluded, which is a gather) + per attention layer
4 x S x (n_heads x head_dim) for the score and value products over the
whole sequence, with no causal halving (PaLM, arXiv:2204.02311, app. B).
Per token = 3 x forward (backward = 2 x forward).  Recomputation is not
counted.  The vocabulary is the real one, not the padded one.
"""


def forward_flops(c: dict, seq_len: int) -> float:
    d, L = c["hidden_size"], c["num_hidden_layers"]
    H, KV, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    ff, V = c["intermediate_size"], c["vocab_size"]
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff
    matmul = L * per_layer + d * V
    return 2.0 * matmul + L * 4.0 * seq_len * H * hd


def flops_per_token(c: dict, seq_len: int) -> float:
    return 3.0 * forward_flops(c, seq_len)
