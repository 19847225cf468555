"""Train step: device self time per step of grad clip and the ZeRO-1 AdamW
update (scope ``opt``, less ``zero1.gather``), averaged over the
chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_ms(run, "opt")
