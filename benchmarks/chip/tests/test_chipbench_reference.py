"""The plain references themselves: the SSD scan against the sequential
recurrence it stands for, and the float8 control's rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip.reference import common, ssm


def _recurrence(X, A, B, C):
    """h_t = exp(A_t) h_{t-1} + X_t B_t^T;  y_t = h_t C_t (per head)."""
    S, H, P = X.shape
    N = B.shape[1]

    def step(h, inp):
        x, a, b, c = inp
        h = jnp.exp(a)[:, None, None] * h + x[:, :, None] * b[None, None, :]
        return h, jnp.einsum("hpn,n->hp", h, c, precision=common.HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N)), (X, A, B, C))
    return y


@pytest.mark.parametrize("Q", [8, 32])
def test_ssd_matches_the_recurrence(Q):
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    S, H, P, N = 64, 3, 4, 5
    X = jax.random.normal(k[0], (S, H, P))
    # log-decays as large as published initial values give (dt*A to -25)
    A = -jnp.exp(jax.random.uniform(k[1], (S, H), minval=-6.0, maxval=3.2))
    B = jax.random.normal(k[2], (S, N))
    C = jax.random.normal(k[3], (S, N))
    got = ssm.ssd(X, A, B, C, Q)
    want = _recurrence(X, A, B, C)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)
    g = jax.grad(lambda a: ssm.ssd(X, a, B, C, Q).sum())(A)
    assert np.all(np.isfinite(np.asarray(g)))


def test_fp8_products_are_coarser_than_bf16_both_ways():
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    a = jax.random.normal(k[0], (64, 128))
    b = jax.random.normal(k[1], (128, 32))
    g = jax.random.normal(k[2], (64, 32))
    f32, fp8 = common.make_mm("f32"), common.make_mm("fp8")

    def errs(mm):
        out, vjp = jax.vjp(lambda x, y: mm("ij,jk->ik", x, y), a, b)
        want, wvjp = jax.vjp(lambda x, y: f32("ij,jk->ik", x, y), a, b)
        rel = lambda u, v: float(jnp.linalg.norm(u - v) / jnp.linalg.norm(v))  # noqa: E731
        return (rel(out, want), *(rel(u, v) for u, v in zip(vjp(g), wvjp(g))))

    bf16 = lambda eq, x, y: f32(eq, x.astype(jnp.bfloat16).astype(jnp.float32),  # noqa: E731
                                y.astype(jnp.bfloat16).astype(jnp.float32))
    for e8, e16 in zip(errs(fp8), errs(bf16)):
        assert e8 > 8 * e16 > 0
