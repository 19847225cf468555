"""Train step: device self time per step of the backward pass (ops JAX names
under ``transpose(``, less the recompute), averaged over the
chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_ms(run, "bwd")
