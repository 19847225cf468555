"""Bucketed sync scheduler (DESIGN.md §7): plan structure, SyncStats
reduction across buckets, and the invariance contract — synced values,
overflow counters, and byte accounting must not depend on ``bucket_bytes``
(including the ``None`` monolithic fallback, which must be bit-exact)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.hypothesis_compat import given, settings, st

from repro.core import buckets as bk
from repro.core import costmodel, metrics
from repro.core.zen import GradSync, SyncConfig

N = 4
TABLE_ROWS, TABLE_D = 256, 8


def _shapes(extra_table=False):
    shapes = {
        "embed": {"table":
                  jax.ShapeDtypeStruct((TABLE_ROWS, TABLE_D), jnp.float32)},
        "mlp": {"w1": jax.ShapeDtypeStruct((32, 16), jnp.float32),
                "w2": jax.ShapeDtypeStruct((16, 32), jnp.float32),
                "b": jax.ShapeDtypeStruct((7,), jnp.float32)},
        "norm": {"g": jax.ShapeDtypeStruct((16,), jnp.float32),
                 "b16": jax.ShapeDtypeStruct((16,), jnp.bfloat16)},
    }
    if extra_table:
        shapes["out_embed"] = {
            "table": jax.ShapeDtypeStruct((64, 4), jnp.float32)}
    return shapes


def _grads(shapes, density=0.1, seed=0):
    """Per-worker gradients matching ``shapes``; tables row-sparse, values
    dyadic so accumulation order cannot perturb bit-exact comparisons."""
    key = jax.random.PRNGKey(seed)

    def leaf(path, s):
        # crc32, not hash(): PYTHONHASHSEED must not change the test data
        name_seed = zlib.crc32(bk.leaf_path_str(path).encode()) % (1 << 30)
        k = jax.random.fold_in(key, name_seed)
        g = jnp.round(jax.random.normal(k, (N, *s.shape)) * 256) / 256
        if "table" in bk.leaf_path_str(path):
            m = metrics.synth_sparse_masks(k, N, s.shape[0], density)
            g = g * m[..., None]
        return g.astype(s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _run(shapes, grads, bucket_bytes, scheme="zen", **kw):
    gs = GradSync(
        SyncConfig(scheme=scheme, density_budget=0.5,
                   bucket_bytes=bucket_bytes),
        ["embed/table", "out_embed/table"], shapes, N,
        data_axis="data", **kw)
    out, stats = jax.vmap(gs, axis_name="data")(grads)
    return gs, out, stats


# ---------------------------------------------------------------------------
# plan structure
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bucket_bytes", [None, 1, 100, 1024, 1 << 20])
def test_plan_covers_all_leaves_once(bucket_bytes):
    gs, _, _ = _run(_shapes(True), _grads(_shapes(True)), bucket_bytes)
    plan = gs.plan
    plan.validate()
    assert plan.n_leaves == len(jax.tree.leaves(_shapes(True)))
    for b in plan.buckets:
        if b.kind == bk.SPARSE:
            # row-sparse leaves are never fused or split
            assert len(b.slots) == 1
            assert "table" in b.slots[0].name
        else:
            # fused dense buckets respect the byte budget...
            if bucket_bytes is not None and len(b.slots) > 1:
                assert b.nbytes <= bucket_bytes
            # ...and never mix dtypes
            assert len({jnp.dtype(s.dtype) for s in b.slots}) == 1


def test_fallback_is_one_bucket_per_leaf():
    gs, _, _ = _run(_shapes(), _grads(_shapes()), None)
    assert len(gs.plan.buckets) == gs.plan.n_leaves
    assert all(len(b.slots) == 1 for b in gs.plan.buckets)


def test_bad_bucket_bytes_rejected():
    with pytest.raises(ValueError, match="bucket_bytes"):
        _run(_shapes(), _grads(_shapes()), 0)


# ---------------------------------------------------------------------------
# invariance to bucket size (the multi-bucket SyncStats reduction contract)
# ---------------------------------------------------------------------------

STAT_KEYS = ("sync/sparse_sent_words", "sync/dense_words", "sync/overflow")


def _assert_invariant(bucket_bytes, scheme="zen", density=0.1):
    shapes = _shapes(True)
    grads = _grads(shapes, density=density)
    _, out0, st0 = _run(shapes, grads, None, scheme)
    _, out1, st1 = _run(shapes, grads, bucket_bytes, scheme)
    for a, b in zip(jax.tree.leaves(out0), jax.tree.leaves(out1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for k in STAT_KEYS:
        np.testing.assert_array_equal(np.asarray(st0[k]), np.asarray(st1[k]))


@pytest.mark.parametrize("bucket_bytes", [1, 64, 257, 1024, 8192, 1 << 22])
@pytest.mark.parametrize("scheme", ["zen", "dense", "auto"])
def test_bucket_bytes_invariance(bucket_bytes, scheme):
    """Synced values bit-exact and overflow/byte accounting identical for
    every bucket size, including the None fallback as the reference."""
    _assert_invariant(bucket_bytes, scheme)


@given(st.integers(min_value=1, max_value=1 << 22))
@settings(max_examples=12, deadline=None)
def test_bucket_bytes_invariance_property(bucket_bytes):
    _assert_invariant(bucket_bytes)


@pytest.mark.parametrize("bucket_bytes", [None, 512, 1 << 20])
def test_zen_dense_parity_per_bucket_size(bucket_bytes):
    """zen == dense trainer-level (no-information-loss) at every bucket
    size: the schedule must not change what is synchronized."""
    shapes = _shapes()
    grads = _grads(shapes)
    _, out_z, _ = _run(shapes, grads, bucket_bytes, "zen")
    _, out_d, _ = _run(shapes, grads, bucket_bytes, "dense")
    for a, b in zip(jax.tree.leaves(out_z), jax.tree.leaves(out_d)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_overflow_surfaces_identically_across_bucket_sizes():
    """Undersized capacity must report the same overflow for every plan."""
    shapes = {"embed": {"table":
                        jax.ShapeDtypeStruct((256, 4), jnp.float32)},
              "w": jax.ShapeDtypeStruct((64,), jnp.float32)}
    grads = _grads(shapes, density=0.9)
    counts = []
    for bb in (None, 128, 1 << 20):
        gs = GradSync(SyncConfig(scheme="zen", density_budget=0.05,
                                 bucket_bytes=bb),
                      ["embed/table"], shapes, N, data_axis="data")
        _, stats = jax.vmap(gs, axis_name="data")(grads)
        counts.append(np.asarray(stats["sync/overflow"]))
    assert int(counts[0].sum()) > 0  # the capacity claim was violated...
    for c in counts[1:]:             # ...and every plan reports it alike
        np.testing.assert_array_equal(counts[0], c)


# ---------------------------------------------------------------------------
# stats reduction + per-tensor scheme selection
# ---------------------------------------------------------------------------

def test_reduce_stats_tags_and_totals():
    shapes = _shapes(True)
    gs, _, stats = _run(shapes, _grads(shapes), 1024, "zen")
    n_sparse = sum(b.kind == bk.SPARSE for b in gs.plan.buckets)
    n_dense = sum(b.kind == bk.DENSE for b in gs.plan.buckets)
    # bucket counts are facts of the plan, not outputs of every step
    counts = gs.plan.counts()
    assert counts["n_buckets"] == len(gs.plan.buckets)
    assert counts["buckets[zen]"] == n_sparse
    assert counts["buckets[dense]"] == n_dense
    assert not any("buckets" in k for k in stats), sorted(stats)
    # dense byte accounting: ring allreduce words over all dense elements
    dense_elems = sum(b.size for b in gs.plan.buckets if b.kind == bk.DENSE)
    want = 2 * (N - 1) / N * dense_elems
    np.testing.assert_allclose(np.asarray(stats["sync/dense_words"])[0],
                               want, rtol=1e-6)


def test_auto_is_per_tensor_not_global():
    """With a measured profile only for one table, 'auto' must pick dense
    for the dense-ish profiled table and zen for the other — per tensor."""
    shapes = _shapes(True)
    dense_profile = costmodel.SparsityProfile(
        M=TABLE_ROWS, d=lambda i: 1.0, s=lambda n: 1.0, vw=TABLE_D)
    gs = GradSync(SyncConfig(scheme="auto", density_budget=0.01),
                  ["embed/table", "out_embed/table"], shapes, N,
                  data_axis="data",
                  profiles={"embed/table": dense_profile})
    schemes_by_name = {b.slots[0].name: b.scheme
                       for b in gs.plan.buckets if b.kind == bk.SPARSE}
    assert schemes_by_name["embed/table"] == "dense"
    assert schemes_by_name["out_embed/table"] == "zen"
