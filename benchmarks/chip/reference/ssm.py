"""Plain float32 reference of a Mamba-2 stack (SSD, arXiv:2405.21060):
pre-norm blocks of z/x/dt/B,C projections, a depthwise causal conv on x,
the state-space dual scan, a gated RMSNorm and the out projection, then a
final RMSNorm and an untied LM head, trained on the mean next-token
cross-entropy.

The scan is the paper's minimal SSD algorithm (``ssd_minimal_discrete``:
exact segment sums within chunks of the published chunk size, a
recurrence over chunk states), written here from the paper and not from
the program, whose chunk size and decomposition differ.  Parameters
arrive as a flat ``{"a/b/c": array}`` dict in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.chip.reference import common

HIGHEST = common.HIGHEST

ARCH_FIELDS = {
    "n_layer": "n_layers", "d_model": "d_model", "vocab_size": "vocab",
    "d_state": "ssm_state", "headdim": "ssm_head_dim", "expand": "ssm_expand",
    "d_conv": "ssm_conv",
}


def init_leaf(path: str, shape, key, c: dict):
    """Published Mamba-2 initial values where the block has its own:
    A = -exp(A_log) with A in [1, 16], dt in [1e-3, 1e-1] log-uniform
    through dt_bias = softplus^-1(dt), D = 1, conv weights and bias
    uniform in +-1/sqrt(d_conv)."""
    name = path.rsplit("/", 1)[-1]
    u = jax.random.uniform(key, shape, jnp.float32)
    if name == "A_log":
        return jnp.log(1.0 + 15.0 * u)
    if name == "dt_bias":
        dt = jnp.exp(jnp.log(1e-3) + u * (jnp.log(1e-1) - jnp.log(1e-3)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "D":
        return jnp.ones(shape, jnp.float32)
    if name in ("conv_w", "conv_b"):
        return (2.0 * u - 1.0) / jnp.sqrt(jnp.float32(c["d_conv"]))
    w = common.init_linear(path, shape, key, c)
    if w is None:
        raise KeyError(f"no initializer for parameter {path!r}")
    return w


def _segsum(x):
    """out[..., i, j] = x[..., j+1] + ... + x[..., i] for i >= j, else -inf."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., :, None], x.shape + (T,))
    xx = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), xx, 0.0)
    ss = jnp.cumsum(xx, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), ss, -jnp.inf)


def ssd(X, A, B, C, Q: int):
    """y_t = sum_{s<=t} (C_t . B_s) exp(A_{s+1} + ... + A_t) X_s.

    X [S, H, P] (input times dt), A [S, H] (dt times A), B, C [S, N]
    (one group, shared by every head).  S must be a multiple of Q."""
    S, H, P = X.shape
    N = B.shape[-1]
    n = S // Q
    X = X.reshape(n, Q, H, P)
    A = A.reshape(n, Q, H).transpose(2, 0, 1)                   # [H, n, Q]
    B = B.reshape(n, Q, N)
    C = C.reshape(n, Q, N)
    A_cs = jnp.cumsum(A, axis=-1)
    G = jnp.einsum("cln,csn->cls", C, B, precision=HIGHEST)
    M = G[None] * jnp.exp(_segsum(A))                            # [H, n, Q, Q]
    y_diag = jnp.einsum("hcls,cshp->clhp", M, X, precision=HIGHEST)
    decay = jnp.exp(A_cs[..., -1:] - A_cs)                      # [H, n, Q]
    states = jnp.einsum("cln,hcl,clhp->chpn", B, decay, X, precision=HIGHEST)
    states = jnp.concatenate([jnp.zeros_like(states[:1]), states], 0)
    chunk_decay = jnp.exp(_segsum(jnp.pad(A_cs[..., -1], ((0, 0), (1, 0)))))
    states = jnp.einsum("hzc,chpn->zhpn", chunk_decay, states,
                        precision=HIGHEST)[:-1]
    y_off = jnp.einsum("cln,chpn,hcl->clhp", C, states, jnp.exp(A_cs),
                       precision=HIGHEST)
    return (y_diag + y_off).reshape(S, H, P)


def trunk(P: dict, tokens, c: dict, mm):
    """Final normed hidden states [S, d] of one row of tokens [S]."""
    S = tokens.shape[0]
    d, N, hp, K = c["d_model"], c["d_state"], c["headdim"], c["d_conv"]
    din = c["expand"] * d
    H = din // hp
    eps = c["rms_norm_eps"]
    Q = c["published"]["chunk_size"]
    pad = (-S) % Q

    def layer(x, lp):
        h = common.rmsnorm(x, lp["ln1"], eps)
        z = mm("sd,de->se", h, lp["mixer/in_z_w"])
        xs = mm("sd,de->se", h, lp["mixer/in_x_w"])
        xp = jnp.pad(xs, ((K - 1, 0), (0, 0)))
        conv = sum(xp[k:k + S] * lp["mixer/conv_w"][k] for k in range(K))
        xs = jax.nn.silu(conv + lp["mixer/conv_b"])
        dt = jax.nn.softplus(mm("sd,dh->sh", h, lp["mixer/in_dt_w"])
                             + lp["mixer/dt_bias"])
        bc = mm("sd,dn->sn", h, lp["mixer/in_bc_w"])
        Bm, Cm = bc[:, :N], bc[:, N:]
        A = -jnp.exp(lp["mixer/A_log"])
        X = xs.reshape(S, H, hp)
        y = ssd(jnp.pad(X * dt[..., None], ((0, pad), (0, 0), (0, 0))),
                jnp.pad(dt * A[None], ((0, pad), (0, 0))),
                jnp.pad(Bm, ((0, pad), (0, 0))),
                jnp.pad(Cm, ((0, pad), (0, 0))), Q)[:S]
        y = (y + X * lp["mixer/D"][None, :, None]).reshape(S, din)
        y = common.rmsnorm(y * jax.nn.silu(z), lp["mixer/norm"], eps)
        return x + mm("se,ed->sd", y, lp["mixer/out_w"]), None

    x = P["embed/table"][tokens]
    x, _ = lax.scan(jax.checkpoint(layer), x, common.layer_params(P))
    return common.rmsnorm(x, P["ln_f"], eps)
