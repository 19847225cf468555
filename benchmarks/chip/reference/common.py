"""Pieces every plain reference shares: float32 matmuls at ``highest``
precision (or, for the control, operands rounded to a lower precision),
RMSNorm, the next-token cross-entropy, global-norm clipping and AdamW.

Nothing here imports the program under test.  The optimizer follows the
published AdamW with bias correction and global-norm clipping, with the
numbers stated in the cell's traffic file.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
NEG = -1e30


# ---------------------------------------------------------------------------
# matmuls: float32, or the control's lower precision
# ---------------------------------------------------------------------------

def _fp8_round(x):
    """Round to float8_e4m3fn with one per-tensor scale (largest magnitude
    to the format's largest value, 448), then back to float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    s = lax.stop_gradient(s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _fp8_einsum(eq: str):
    """einsum ``eq`` taken in float8 both ways: the operands rounded on the
    way forward, the incoming cotangent rounded on the way back, every
    product summed in float32 at ``highest``."""
    def f(a, b):
        return jnp.einsum(eq, a, b, precision=HIGHEST)

    @jax.custom_vjp
    def mm(a, b):
        return f(_fp8_round(a), _fp8_round(b))

    def fwd(a, b):
        qa, qb = _fp8_round(a), _fp8_round(b)
        return f(qa, qb), (qa, qb)

    def bwd(res, g):
        return jax.vjp(f, *res)[1](_fp8_round(g))

    mm.defvjp(fwd, bwd)
    return mm


def make_mm(precision: str):
    """``mm(eq, a, b)``: a float32 einsum at ``highest``.  With
    ``precision="fp8"`` every product is taken in float8 e4m3 (per-tensor
    scaled) in the forward and the backward pass: the control, one step
    below the bfloat16 the configurations state."""
    if precision == "f32":
        def mm(eq, a, b):
            return jnp.einsum(eq, a, b, precision=HIGHEST)
        return mm
    if precision == "fp8":
        by_eq = {}

        def mm(eq, a, b):
            if eq not in by_eq:
                by_eq[eq] = _fp8_einsum(eq)
            return by_eq[eq](a, b)
        return mm
    raise ValueError(f"unknown reference precision {precision!r}")


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------

def rmsnorm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def head_nll(x, w_head, labels, vocab: int, mm, chunk: int = 512):
    """Sum of next-token negative log-likelihoods and the count of labels
    >= 0.  x [T, d] (every row's tokens, flattened), w_head [d, Vp]
    (columns >= vocab are padding and take no part), labels [T].  Scanned
    over chunks of tokens so the logits of one chunk at a time exist."""
    T = x.shape[0]
    c = min(chunk, T)
    n = -(-T // c)
    pad = n * c - T
    xs = jnp.pad(x, ((0, pad), (0, 0))).reshape(n, c, -1)
    ls = jnp.pad(labels, (0, pad), constant_values=-1).reshape(n, c)
    valid = jnp.arange(w_head.shape[1]) < vocab

    @jax.checkpoint
    def body(acc, inp):
        xb, lb = inp
        logits = mm("sd,dv->sv", xb, w_head)
        logits = jnp.where(valid[None], logits, NEG)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.clip(lb, 0, None)[:, None], axis=-1)[:, 0]
        ok = (lb >= 0).astype(jnp.float32)
        return (acc[0] + jnp.sum((lse - picked) * ok), acc[1] + jnp.sum(ok)), None

    (s, cnt), _ = lax.scan(body, (jnp.float32(0), jnp.float32(0)), (xs, ls))
    return s, cnt


def batch_nll(P: dict, tokens, labels, c: dict, mm, trunk):
    """(NLL sum, label count) over a batch: tokens, labels [B, S].  The
    kind's ``trunk(P, tokens[S], c, mm)`` gives one row's final hidden
    states; the head runs over all rows' tokens in chunks."""
    x = jax.vmap(lambda t: trunk(P, t, c, mm))(tokens)
    x = x.reshape(-1, x.shape[-1])
    return head_nll(x, P["lm_head_w"], labels.reshape(-1), c["vocab_size"], mm)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def global_norm(tree) -> jnp.ndarray:
    return jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(tree)))


@functools.partial(jax.jit, static_argnames=("opt", "stored"),
                   donate_argnums=(0, 2))
def adamw(params, grads, state, step, opt: tuple, stored: tuple):
    """One clipped AdamW step on float32 copies of the stored values.

    ``state`` is ``{"m": {...}, "v": {...}}`` in float32; ``opt`` the
    traffic file's optimizer numbers as sorted (key, value) pairs;
    ``stored`` the (leaf, dtype name) pairs the configuration stores each
    parameter in: the new value is rounded to that dtype, as a trainer
    that keeps its parameters there must.  Returns (params, state, the
    clipped gradient's per-leaf norms)."""
    o, dt = dict(opt), dict(stored)
    gn = global_norm(grads)
    scale = (jnp.minimum(1.0, o["grad_clip"] / (gn + 1e-9))
             if o["grad_clip"] > 0 else jnp.float32(1.0))
    t = step + 1.0
    new_p, new_m, new_v, gnorms = {}, {}, {}, {}
    for k, p in params.items():
        g = grads[k] * scale
        gnorms[k] = jnp.sqrt(jnp.sum(g * g))
        m = o["b1"] * state["m"][k] + (1 - o["b1"]) * g
        v = o["b2"] * state["v"][k] + (1 - o["b2"]) * g * g
        upd = (m / (1 - o["b1"] ** t)) / (jnp.sqrt(v / (1 - o["b2"] ** t))
                                          + o["eps"]) + o["weight_decay"] * p
        new_p[k] = (p - o["lr"] * upd).astype(dt[k]).astype(jnp.float32)
        new_m[k], new_v[k] = m, v
    return new_p, {"m": new_m, "v": new_v}, gnorms


# ---------------------------------------------------------------------------
# the benchmark's weights (made from the seed; given to program and reference)
# ---------------------------------------------------------------------------

def init_linear(path: str, shape, key, c: dict):
    """Values shared by every kind: ones for norm gains, N(0, 0.02) for the
    input table and biases, N(0, 1/fan_in) for matrices; padded vocabulary
    rows of the table and columns of the head are zero.  ``None`` for a
    leaf this rule does not know."""
    name = path.rsplit("/", 1)[-1]
    vocab = c["vocab_size"]
    normal = jax.random.normal(key, shape, jnp.float32)
    if name in ("ln1", "ln2", "ln_f", "norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "table":
        return jnp.where(jnp.arange(shape[0])[:, None] < vocab, normal * 0.02, 0.0)
    if name == "lm_head_w":
        w = normal / jnp.sqrt(jnp.float32(shape[0]))
        return jnp.where(jnp.arange(shape[1])[None] < vocab, w, 0.0)
    if name.endswith("_b"):
        return normal * 0.02
    if name.endswith("_w"):
        return normal / jnp.sqrt(jnp.float32(shape[-2]))
    return None


def layer_params(P: dict, prefix: str = "layers/") -> dict:
    return {k[len(prefix):]: v for k, v in P.items() if k.startswith(prefix)}
