"""Device collectives: milliseconds per step in which a collective op
(all-reduce, all-gather, all-to-all, reduce-scatter, collective-permute,
sync or async) was in flight on a device, averaged over the chips."""
from benchmarks.chip import tracing


def reduce(run):
    if run.trace is None or not run.traced_steps:
        return None
    devs = sorted(run.trace.ops)
    total = sum(tracing.length(tracing.collective_ns(run.trace, d)[0])
                for d in devs)
    if total <= 0:
        return None
    return total / len(devs) / run.traced_steps / 1e6
