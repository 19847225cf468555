"""In-kernel building blocks that Mosaic (the TPU Pallas compiler) lowers.

``jnp.cumsum`` has no Mosaic lowering, and a 1-D vector cannot be
re-oriented by indexing (``row[0, :, None]``).  The kernels of this
package therefore share two primitives:

* :func:`prefix_count` — the inclusive prefix sum of a ``[1, L]`` row of
  small non-negative integers, as two MXU matmuls against 0/1 triangular
  matrices: the row is folded to ``[L/128, 128]``, each 128-lane segment
  is scanned by ``x @ U`` (``U[i, j] = i <= j``) and the segment totals
  are carried by ``T @ carry`` (``T[r, r'] = r' < r``).  Operands are
  bf16 (0/1 matrices and counts <= 128 are exact in bf16) and the MXU
  accumulates in f32, which is exact below 2**24 — so the result is
  bit-exact, in interpret mode and on the chip alike.
* :func:`column` / :func:`row` — a ``[1, L]`` row as an ``[L, 1]``
  column and back, through a 2-D tile transpose (Mosaic refuses most
  reshapes that move data between lanes and sublanes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def _tri(n: int, keep) -> jnp.ndarray:
    """[n, n] bf16 0/1 matrix: 1 where ``keep(row, col)``."""
    r = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return keep(r, c).astype(jnp.bfloat16)


def prefix_count(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum of a ``[1, L]`` row of 0/1 flags (bool or
    int), ``L`` a multiple of 128 and below 2**24 -> int32 ``[1, L]``."""
    L = x.shape[1]
    assert x.shape[0] == 1 and L % LANES == 0, x.shape
    R = L // LANES
    seg = x.astype(jnp.bfloat16).reshape(R, LANES)
    inc = jnp.dot(seg, _tri(LANES, lambda r, c: r <= c),
                  preferred_element_type=jnp.float32)       # [R, 128]
    if R > 1:
        tot = jnp.broadcast_to(inc[:, LANES - 1:], (R, LANES))
        # carry[r] = sum of the totals of segments r' < r
        carry = jnp.dot(_tri(R, lambda r, c: c < r),
                        tot.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        inc = inc + carry
    return inc.astype(jnp.int32).reshape(1, L)


def column(x: jnp.ndarray) -> jnp.ndarray:
    """``[1, L]`` row -> ``[L, 1]`` column, as an (8, L) -> (L, 8) tile
    transpose (bool rows go through int32)."""
    if x.dtype == jnp.bool_:
        return column(x.astype(jnp.int32)) != 0
    return jnp.broadcast_to(x, (8, x.shape[1])).T[:, :1]


def row(x: jnp.ndarray) -> jnp.ndarray:
    """``[L, 1]`` column -> ``[1, L]`` row, as an (L, 128) -> (128, L)
    tile transpose (bool columns go through int32)."""
    if x.dtype == jnp.bool_:
        return row(x.astype(jnp.int32)) != 0
    return jnp.broadcast_to(x, (x.shape[0], LANES)).T[:1, :]
