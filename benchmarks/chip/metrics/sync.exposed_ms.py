"""Gradient sync: the part of ``sync.exchange_ms`` in which no op outside
the sync layers ran on that device, per step, averaged over the chips
(``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.sync_exposed_ms(run)
