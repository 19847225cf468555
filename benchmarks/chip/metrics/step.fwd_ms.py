"""Train step: device self time per step of the forward pass (scope ``fwd``:
``model.train_loss`` inside ``value_and_grad``), averaged over the
chips (``scopes.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.layer_ms(run, "fwd")
