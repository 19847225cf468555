"""ZeRO-1: milliseconds per step in which the all-gather of the updated
parameter chunks (scope ``zero1.gather``) ran or was in flight, sync or
async, averaged over the chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_in_flight_ms(run, "zero1.gather")
