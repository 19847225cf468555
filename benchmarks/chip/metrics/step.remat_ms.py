"""Train step: device self time per step of the forward recomputed inside
the backward by ``jax.checkpoint`` (``rematted_computation``), averaged over the
chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_ms(run, "remat")
