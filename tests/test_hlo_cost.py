"""The trip-count-aware HLO walker against closed-form ground truth."""
import jax
import jax.numpy as jnp
import pytest
from jax import lax

from repro.launch import hlo_cost


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_scan_trip_count_multiplies_flops():
    def scanned(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        y, _ = lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)
    r = hlo_cost.analyze(_compile(scanned, x, ws).as_text())
    want = 2 * 128 ** 3 * 7
    assert abs(r["flops"] - want) / want < 0.02


def test_nested_scan():
    def nested(x, ws):
        def outer(c, grp):
            def inner(c2, w):
                return jnp.tanh(c2 @ w), None
            c, _ = lax.scan(inner, c, grp)
            return c, None
        y, _ = lax.scan(outer, x, ws.reshape(3, 4, 128, 128))
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((12, 128, 128), jnp.float32)
    r = hlo_cost.analyze(_compile(nested, x, ws).as_text())
    want = 2 * 128 ** 3 * 12
    assert abs(r["flops"] - want) / want < 0.02


def test_xla_cost_analysis_undercounts_scans():
    """Regression guard for WHY the walker exists."""
    def scanned(x, ws):
        def body(c, w):
            return c @ w, None
        y, _ = lax.scan(body, x, ws)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    comp = _compile(scanned, x, ws)
    xla = comp.cost_analysis().get("flops", 0.0)
    walker = hlo_cost.analyze(comp.as_text())["flops"]
    assert walker > 5 * xla  # XLA counts the body once


def test_collective_wire_factors():
    hlo = """
ENTRY %main (p: f32[64]) -> f32[64] {
  %p = f32[64]{0} parameter(0)
  %ar = f32[64]{0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %ag = f32[64]{0} all-gather(%ar), replica_groups={{0,1}}, dimensions={0}
}
"""
    r = hlo_cost.analyze(hlo)
    # all-reduce: 2*(4-1)/4 * 256B = 384; all-gather: (2-1)/2 * 256B = 128
    assert r["collectives"]["all-reduce"] == pytest.approx(384)
    assert r["collectives"]["all-gather"] == pytest.approx(128)


def test_exclude_bytes_re():
    def f(x):
        with jax.named_scope("flash_fusable"):
            y = x @ x          # standalone dot carrying the scope metadata
        return y @ x

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    txt = _compile(f, x).as_text()
    full = hlo_cost.analyze(txt)["bytes"]
    excl = hlo_cost.analyze(txt, exclude_bytes_re="flash_fusable")["bytes"]
    assert excl < full
