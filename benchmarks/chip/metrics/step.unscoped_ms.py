"""Train step: device self time per step of ops in no layer: what XLA made
with no ``op_name`` in the entry computation, and the metrics ``pmean``, averaged over the
chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_ms(run, "unscoped")
