"""Model FLOPs per trained token of a Mamba-2 (SSD) stack.

forward = 2 x (matmul parameters: z, x, dt, B/C and out projections and
the LM head; the input embedding table, a gather, and the depthwise conv
excluded) + per layer the chunked SSD algorithm's matmul work per token,
with chunk Q (the published chunk_size), state N, head dim P, H heads and
one B/C group:

    2 Q N      C_i . B_j within the chunk (shared by the heads)
    2 Q P H    the masked (C B^T o decay) products with x within the chunk
    2 N P H    the chunk state's contribution to each output
    2 N P H    each token's contribution to the chunk state

Per token = 3 x forward.  Recomputation is not counted.
"""


def forward_flops(c: dict, seq_len: int) -> float:
    d, L, V = c["d_model"], c["n_layer"], c["vocab_size"]
    N, P = c["d_state"], c["headdim"]
    din = c["expand"] * d
    H = din // P
    Q = c["published"]["chunk_size"]
    per_layer = 2 * d * din + d * H + d * 2 * N + din * d
    ssd = 2 * Q * N + 2 * Q * P * H + 4 * N * P * H
    return 2.0 * (L * per_layer + d * V) + L * ssd


def flops_per_token(c: dict, seq_len: int) -> float:
    return 3.0 * forward_flops(c, seq_len)
