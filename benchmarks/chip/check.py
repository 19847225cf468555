"""What decides ``correct``: the timed program's first steps against the
plain float32 reference, from the same weights and the same batches.

Three numbers, each with a limit of its own (``limits/<cell>.json``):

* ``loss_gap``: the largest relative gap, over the checked steps, between
  the loss the program reported and the reference's loss.
* ``grad_gap``: the first step's gradient as the optimizer got it (read
  from AdamW's first moment after one step, m = (1 - b1) g), leaf by leaf:
  |‖g‖ - ‖g_ref‖| over the larger of ‖g_ref‖ and the median leaf's
  ‖g_ref‖; the worst leaf.
* ``change_gap``: the same for each parameter's change after the checked
  steps, ‖p - p0‖.

Leaves whose reference gradient is under a thousandth of the median
leaf's are left out of both leaf-wise numbers: their gradient is nought to
rounding (a key bias under softmax), and AdamW moves them by round-off.
"""
from __future__ import annotations

import dataclasses
import importlib
import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference import common

NUMBERS = ("loss_gap", "grad_gap", "change_gap")
SMALL_LEAF = 1e-3


@dataclasses.dataclass
class Readings:
    losses: list           # per checked step
    grad_norms: dict       # {leaf path: norm}
    change_norms: dict     # {leaf path: norm}


def leaf_paths(tree) -> list:
    out = []
    for kp, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out.append("/".join(str(getattr(k, "key", k)) for k in kp))
    return out


def flat(tree) -> dict:
    """{leaf path: leaf} of a parameter pytree."""
    return dict(zip(leaf_paths(tree), jax.tree_util.tree_leaves(tree)))


def seed_key(seed: int):
    """A PRNG key from any whole-number seed (more than 32 bits allowed)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def reference_module(kind: str):
    return importlib.import_module(f"benchmarks.chip.reference.{kind}")


def init_values(kind: str, c: dict, shapes: dict, key):
    """{path: initial value in its stored dtype} for the given
    {path: (shape, dtype)}: the benchmark's weights, drawn by the kind's
    ``init_leaf`` from one key per leaf."""
    ref = reference_module(kind)
    return {p: ref.init_leaf(p, s, leaf_key(key, p), c).astype(dt)
            for p, (s, dt) in shapes.items()}


# ---------------------------------------------------------------------------
# the reference, three steps from the seed
# ---------------------------------------------------------------------------

def fault_batch(tokens, labels, fault: str | None, dp: int):
    """The reference's batch under a planted fault: ``half`` leaves out
    the second half of the rows (labels -1, the mean taken over the rest);
    ``no_exchange`` trains on the first worker's rows alone, as a worker
    whose gradient never left it would."""
    if fault == "half":
        labels = labels.copy()
        labels[labels.shape[0] // 2:] = -1
    elif fault == "no_exchange":
        rows = tokens.shape[0] // dp
        tokens, labels = tokens[:rows], labels[:rows]
    elif fault is not None:
        raise ValueError(f"unknown fault {fault!r}")
    return tokens, labels


def reference_readings(kind: str, c: dict, initial: dict, batches: list,
                       opt: dict, *, precision: str = "f32",
                       fault: str | None = None, dp: int = 1,
                       devices=None) -> Readings:
    """Readings of the plain reference from the benchmark's weights.

    ``initial`` {path: array in its stored dtype} are the very weights the
    program started from (the same compiled call made them again);
    ``batches`` the host (tokens, labels) of each checked step.  The
    gradient is taken on ``devices[0]`` and the optimizer runs on
    ``devices[-1]`` (the same chip where there is one), so the two never
    hold their state together."""
    ref = reference_module(kind)
    mm = common.make_mm(precision)
    devices = devices or jax.devices()[:1]
    gdev, odev = devices[0], devices[-1]
    p0 = jax.device_put(initial, gdev)
    stored = tuple(sorted((p, jnp.dtype(v.dtype).name) for p, v in p0.items()))
    optp = tuple(sorted(opt.items()))
    P = jax.jit(lambda p: {k: v.astype(jnp.float32) for k, v in p.items()})(p0)

    @jax.jit
    def grad_fn(P, tokens, labels):
        def loss(P):
            s, n = common.batch_nll(P, tokens, labels, c, mm, ref.trunk)
            return s / n, n
        (lv, _), g = jax.value_and_grad(loss, has_aux=True)(P)
        return lv, g

    state = None
    losses, gnorms0 = [], None
    for t, (tokens, labels) in enumerate(batches):
        tokens, labels = fault_batch(tokens, labels, fault, dp)
        lv, g = grad_fn(P, jnp.asarray(tokens), jnp.asarray(labels))
        losses.append(float(lv))
        P, g = jax.device_put((P, g), odev)
        if state is None:
            state = jax.tree.map(jnp.zeros_like, {"m": g, "v": g})
        P, state, gn = common.adamw(P, g, state, jnp.float32(t), optp, stored)
        if gnorms0 is None:
            gnorms0 = {k: float(v) for k, v in gn.items()}
        del g
        P = jax.device_put(P, gdev)
    del state
    cn = {k: float(v) for k, v in change_norms(P, p0).items()}
    return Readings(losses=losses, grad_norms=gnorms0, change_norms=cn)


@jax.jit
def change_norms(p: dict, p0: dict) -> dict:
    """{path: ‖p - p0‖} in float32."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(p[k].astype(jnp.float32)
                                           - p0[k].astype(jnp.float32))))
            for k in p0}


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------

def _leaf_gap(got: dict, want: dict, grads_ref: dict) -> tuple:
    med = float(np.median(list(want.values())))
    gmed = float(np.median(list(grads_ref.values())))
    worst, leaf = 0.0, ""
    for k, w in want.items():
        if grads_ref[k] < SMALL_LEAF * gmed:
            continue
        g = got[k]
        gap = abs(g - w) / max(w, med) if math.isfinite(g) else math.inf
        if gap > worst or not math.isfinite(gap):
            worst, leaf = gap, k
    return worst, leaf


def compare(prog: Readings, ref: Readings) -> dict:
    """{number: (value, where)} for the three numbers of the module doc."""
    lg, step = 0.0, 0
    for i, (a, b) in enumerate(zip(prog.losses, ref.losses)):
        gap = abs(a - b) / abs(b) if math.isfinite(a) else math.inf
        if gap > lg or not math.isfinite(gap):
            lg, step = gap, i
    gg, gleaf = _leaf_gap(prog.grad_norms, ref.grad_norms, ref.grad_norms)
    cg, cleaf = _leaf_gap(prog.change_norms, ref.change_norms, ref.grad_norms)
    return {"loss_gap": (lg, f"step {step + 1}"), "grad_gap": (gg, gleaf),
            "change_gap": (cg, cleaf)}


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, checks): every number at or under its limit."""
    checks = {k: {"value": numbers[k][0], "limit": limits[k]} for k in NUMBERS}
    ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return ok, checks
