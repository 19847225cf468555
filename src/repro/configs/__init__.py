"""Assigned-architecture registry: ``get_config(name)`` / ``ALL_ARCHS``."""
from repro.configs import (
    olmoe_1b_7b, whisper_medium, qwen2_0_5b, phi3_5_moe, phi4_mini,
    mamba2_370m, zamba2_1_2b, pixtral_12b, qwen2_5_3b, minicpm3_4b,
    deepseek_v2_lite,
)

_MODULES = [
    olmoe_1b_7b, whisper_medium, qwen2_0_5b, phi3_5_moe, phi4_mini,
    mamba2_370m, zamba2_1_2b, pixtral_12b, qwen2_5_3b, minicpm3_4b,
    deepseek_v2_lite,
]

# published sizes, then one chip's share of a stated deployment
CONFIGS = {c.name: c for c in [m.CONFIG for m in _MODULES]
           + [deepseek_v2_lite.EP8_SHARE]}
ALL_ARCHS = list(CONFIGS)

# input shapes assigned to this paper
INPUT_SHAPES = {
    "train_4k": dict(seq_len=4096, global_batch=256, mode="train"),
    "prefill_32k": dict(seq_len=32768, global_batch=32, mode="prefill"),
    "decode_32k": dict(seq_len=32768, global_batch=128, mode="decode"),
    "long_500k": dict(seq_len=524288, global_batch=1, mode="decode"),
}


def get_config(name: str):
    if name not in CONFIGS:
        raise KeyError(f"unknown arch '{name}'; known: {ALL_ARCHS}")
    return CONFIGS[name]
