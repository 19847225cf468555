"""Device: share of the traced window in which no op ran, averaged over
the chips used, in percent."""
from benchmarks.chip import tracing


def reduce(run):
    if run.trace is None or not run.trace.ops:
        return None
    return 100.0 * (1.0 - tracing.busy_s(run.trace) / tracing.window_s(run.trace))
