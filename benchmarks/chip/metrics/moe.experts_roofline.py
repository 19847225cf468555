"""Kernels: the held experts' grouped matmuls against their roofline, in
percent.  Operations per step: the program's count of token-expert pairs
computed on held experts (``moe/held_pairs``, summed over the layers,
averaged over the window's steps) times ``flops/moe.py``'s
``PAIR_PASSES x pair_flops`` (three projections, forward, recomputed
forward and twice in backward); over ``moe.experts_ms`` times the chip's
bf16 peak.  The matmuls are bound by operations: their bytes (weights
and the held rows) take under half the time the operations do at
``peaks.json``'s bandwidth."""
from benchmarks.chip import blocks
from benchmarks.chip.flops import moe


def reduce(run):
    ms = blocks.block_ms(run, "moe.experts")
    pairs = [c["moe/held_pairs"] for c in run.counters if "moe/held_pairs" in c]
    if not ms or not pairs:
        return None
    flops = moe.PAIR_PASSES * moe.pair_flops(run.cell.config) * sum(pairs) / len(pairs)
    return 100.0 * flops / (ms / 1e3 * run.peak["bf16_flops_per_s"])
