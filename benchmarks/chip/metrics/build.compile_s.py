"""Program build: seconds JAX spent tracing, lowering and compiling (or
reading from the persistent cache) up to the end of the traced tail
(JAX's compile events, summed by ``launch/compile_cache.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.compile_value(run, "seconds")
