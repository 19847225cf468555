"""Train step: model FLOPs per token (flops/<kind>.py) times the measured
window's tokens per second, over the chips' bf16 peak, in percent.
Recomputation is not counted."""


def reduce(run):
    peak = run.peak["bf16_flops_per_s"] * run.chips
    return 100.0 * run.flops_per_token * run.tokens_per_s / peak
