"""Gradient sync: milliseconds per step in which an op of GradSync's
exchange (scope ``sync.exchange``: the per-bucket collectives and decode,
the dense-bucket ``psum`` among them) ran or was in flight, averaged over
the chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_in_flight_ms(run, "sync.exchange")
