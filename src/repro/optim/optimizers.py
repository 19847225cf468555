"""Elementwise optimizers (ZeRO-1 friendly).

Under ZeRO-1 the trainer hands each rank a slice of every leaf's leading
axis, in the leaf's own dtype, with f32 moments of the same shape; these
update rules are shape-agnostic (f32 arithmetic, the parameter rounded to
its own dtype once) so they work on full leaves and chunks alike.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    kind: str = "adamw"      # adamw | sgd
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0   # global-norm clip (0 = off)


def adamw_init(p: jnp.ndarray) -> dict:
    return {
        "m": jnp.zeros(p.shape, jnp.float32),
        "v": jnp.zeros(p.shape, jnp.float32),
    }


def adamw_update(cfg: OptConfig, p, g, st, step):
    g = g.astype(jnp.float32)
    pf = p.astype(jnp.float32)
    m = cfg.b1 * st["m"] + (1 - cfg.b1) * g
    v = cfg.b2 * st["v"] + (1 - cfg.b2) * g * g
    t = step.astype(jnp.float32) + 1.0
    mh = m / (1 - cfg.b1 ** t)
    vh = v / (1 - cfg.b2 ** t)
    upd = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * pf
    return (pf - cfg.lr * upd).astype(p.dtype), {"m": m, "v": v}


def sgd_init(p: jnp.ndarray) -> dict:
    return {"mom": jnp.zeros(p.shape, jnp.float32)}


def sgd_update(cfg: OptConfig, p, g, st, step):
    del step
    mom = 0.9 * st["mom"] + g.astype(jnp.float32)
    return (p.astype(jnp.float32) - cfg.lr * mom).astype(p.dtype), {"mom": mom}


INITS = {"adamw": adamw_init, "sgd": sgd_init}
UPDATES = {"adamw": adamw_update, "sgd": sgd_update}


def ef_residual_init(struct):
    """Zero error-feedback residual memory from its ShapeDtypeStruct tree.

    The EF residual (core/sparsify.py, DESIGN.md §8) is optimizer state —
    initialized here, checkpointed with the moments, threaded through
    every update — but unlike the moments it is per-device and never
    ZeRO-chunked: compression consumes the *local* bucket payload before
    the ZeRO-1 update partitions anything, so chunking it would hand each
    rank the wrong memory."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), struct)


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(l.astype(jnp.float32) ** 2) for l in leaves))
