"""Device collectives: the part of ``collectives.ms`` during which no other
op ran on that device, per step, averaged over the chips."""
from benchmarks.chip import tracing


def reduce(run):
    if run.trace is None or not run.traced_steps:
        return None
    devs = sorted(run.trace.ops)
    if sum(tracing.length(tracing.collective_ns(run.trace, d)[0]) for d in devs) <= 0:
        return None
    exposed = sum(tracing.length(tracing.collective_ns(run.trace, d)[1])
                  for d in devs)
    return exposed / len(devs) / run.traced_steps / 1e6
