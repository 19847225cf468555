"""Program build: executables the process obtained up to the end of the
traced tail, each a backend compile or a persistent-cache hit (JAX's
compile events, counted by ``launch/compile_cache.py``)."""
from benchmarks.chip import scopes


def reduce(run):
    return scopes.compile_value(run, "executables")
