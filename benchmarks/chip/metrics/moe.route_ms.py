"""Expert layer: device self time per step of the block ``moe.route`` (the
router, top-k, sort, dispatch gather, combine and balance loss of every
expert layer), in the forward, the recomputed forward and the backward
together, averaged over the chips (``blocks.py``)."""
from benchmarks.chip import blocks


def reduce(run):
    return blocks.block_ms(run, "moe.route")
