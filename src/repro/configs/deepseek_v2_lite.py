"""DeepSeek-V2-Lite (15.7B total / 2.4B active): MLA without query
compression (YaRN rope), one leading dense layer, then DeepSeekMoE layers
of 2 shared + 64 routed experts, top-6, gates not renormalised
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json].

``EP8_SHARE`` is one chip's share of DeepSeek-V2's 8-way expert-parallel
deployment, cut in depth as a pipeline stage would be: the dense layer and
4 MoE layers, experts 0-7 of each layer's 64, and the first eighth of the
vocabulary.  Every width and the router's 64 outputs are as published."""
import dataclasses

from repro.models.common import ArchConfig, Yarn

CONFIG = ArchConfig(
    name="deepseek-v2-lite", kind="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv=16, head_dim=128,
    d_ff=10944, vocab=102400, rope_theta=10_000.0,
    n_experts=64, top_k=6, d_ff_expert=1408, n_shared_experts=2,
    first_k_dense=1, norm_topk=False, aux_alpha=0.001, seq_aux=True,
    mla_kv_rank=512, mla_rope_dim=64, mla_v_dim=128,
    yarn=Yarn(factor=40.0, original=4096, beta_fast=32.0, beta_slow=1.0,
              mscale=0.707, mscale_all_dim=0.707),
    norm_eps=1e-6,
    source="arXiv:2405.04434",
)

EP8_SHARE = dataclasses.replace(
    CONFIG, name="deepseek-v2-lite-ep8", n_layers=5, experts_held=8,
    vocab=12800)
