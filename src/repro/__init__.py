"""repro: sparsity-driven gradient-synchronization reproduction.

Importing this package enables ``jax_threefry_partitionable``.  The TP
mesh-invariance contract (DESIGN.md §9) requires ``jax.random`` bits to be
a pure function of (key, shape) regardless of how the result — or the
computation producing it — is sharded.  The legacy (non-partitionable)
threefry lowering does not guarantee that: a ``[rows, d]`` normal draw
materialized under a ``P('model', None)`` out-sharding produces different
bits on a (2, 4) mesh than on (1, 1), which made parameter initialization
mesh-dependent and broke cross-mesh loss parity for every sync scheme
(dense included).  Current jax releases default the flag on; setting it
here keeps the contract explicit.
"""
import jax

jax.config.update("jax_threefry_partitionable", True)
