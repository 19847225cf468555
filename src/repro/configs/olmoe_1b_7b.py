"""OLMoE-1B-7B: 64-expert top-8 MoE [arXiv:2409.02060].

How the program runs it (models/moe.py, DESIGN.md §16): the router's
logits, softmax and top-k in float32; every pair routed to a held expert
computed (dropless; ``capacity_factor`` bounds only ``moe_a2a``'s
exchange, so ``moe/dropped`` is reported there alone); the Switch-style
balance loss over the whole batch, averaged over layers, weight 0.01."""
from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="olmoe-1b-7b", kind="moe",
    n_layers=16, d_model=2048, n_heads=16, n_kv=16, head_dim=128,
    d_ff=1024, vocab=50304, n_experts=64, top_k=8,
    source="arXiv:2409.02060",
)
