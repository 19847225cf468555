"""Gradient sync: device self time per step of GradSync's local work
(scope ``sync.encode``: bucket packing and unpacking, the compress hook,
zen's encode), averaged over the chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_ms(run, "sync.encode")
