"""Error-feedback sparsification stack (DESIGN.md §8).

The contracts that make induced sparsity safe to train with:
  * the EF invariant — sent + residual' == grad + residual, exactly;
  * bit-exact determinism under jit, identity under vmap (no cross-worker
    leakage through the residual);
  * residual state survives a checkpoint round-trip through
    ``checkpoint/io.py`` bit-exactly;
  * convergence: top-k WITH error feedback converges on a toy quadratic
    where plain top-k provably stalls (worker-wise cancellation);
  * the adaptive density controller flips dense<->zen from MEASURED
    densities, per bucket.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import buckets as bk
from repro.core import sparsify
from repro.core.sparsify import (
    CompressConfig,
    DensityController,
    compress_bucket,
    parse_compress,
)
from repro.core.zen import GradSync, SyncConfig
from repro.checkpoint import io as ckpt_io

N = 4


# ---------------------------------------------------------------------------
# config parsing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec, kind, ef", [
    ("topk:0.01", "topk", True),
    ("randk:0.05", "randk", True),
    ("topk:0.02:noef", "topk", False),
    ("threshold:1e-3", "threshold", True),
    ("none", "none", True),
])
def test_parse_compress(spec, kind, ef):
    cfg = parse_compress(spec)
    assert cfg.kind == kind and cfg.ef == ef
    # tag() round-trips through the parser (the bucket plan stores tags)
    assert parse_compress(cfg.tag()) == cfg


@pytest.mark.parametrize("bad", ["topk", "topk:0", "topk:2.0", "magic:0.1",
                                 "topk:0.1:what"])
def test_parse_compress_rejects(bad):
    with pytest.raises(ValueError):
        parse_compress(bad)


# ---------------------------------------------------------------------------
# the sparsifiers + EF invariant
# ---------------------------------------------------------------------------

def _payload(size=512, seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), (size,)).astype(dtype)


def test_topk_keeps_exactly_k():
    cfg = CompressConfig(kind="topk", density=0.05)
    g = _payload(400)
    sent, res, d1 = compress_bucket(cfg, g, jnp.zeros(400))
    k = cfg.keep_count(400)
    assert int(jnp.sum(sent != 0)) == k
    assert float(d1) == pytest.approx(k / 400)
    # the kept elements are the largest-|g| ones
    kept = np.flatnonzero(np.asarray(sent))
    top = np.argsort(-np.abs(np.asarray(g)))[:k]
    assert set(kept) == set(top)


@pytest.mark.parametrize("kind", ["topk", "threshold", "randk"])
def test_ef_invariant_exact(kind):
    """sent + residual' == payload + residual in f32, bit-exact: EF moves
    information, never loses it."""
    cfg = CompressConfig(kind=kind, density=0.1, threshold=0.5)
    g = _payload(300, seed=1)
    r = _payload(300, seed=2) * 0.1
    key = jax.random.PRNGKey(7)
    sent, r2, _ = compress_bucket(cfg, g, r, key=key)
    np.testing.assert_array_equal(
        np.asarray(sent.astype(jnp.float32) + r2), np.asarray(g + r))


def test_ef_invariant_bf16_payload():
    """With a bf16 payload the residual must compensate against the CAST
    wire values, so the f32 invariant still holds exactly."""
    cfg = CompressConfig(kind="topk", density=0.1)
    g = _payload(256, seed=3, dtype=jnp.bfloat16)
    r = _payload(256, seed=4) * 0.01
    sent, r2, _ = compress_bucket(cfg, g, r)
    assert sent.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(sent.astype(jnp.float32) + r2),
        np.asarray(g.astype(jnp.float32) + r))


def test_jit_deterministic_and_matches_eager():
    cfg = CompressConfig(kind="topk", density=0.03)
    g, r = _payload(1024, seed=5), _payload(1024, seed=6) * 0.1
    jitted = jax.jit(lambda g_, r_: compress_bucket(cfg, g_, r_))
    a = jitted(g, r)
    b = jitted(g, r)
    c = compress_bucket(cfg, g, r)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(a, c):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_randk_deterministic_in_key():
    cfg = CompressConfig(kind="randk", density=0.2)
    g = _payload(512)
    k1, k2 = jax.random.PRNGKey(0), jax.random.PRNGKey(1)
    s1, _, _ = compress_bucket(cfg, g, None, key=k1)
    s1b, _, _ = compress_bucket(cfg, g, None, key=k1)
    s2, _, _ = compress_bucket(cfg, g, None, key=k2)
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s1b))
    assert np.any(np.asarray(s1) != np.asarray(s2))


def test_vmap_is_identity_per_worker():
    """vmapped compression == stacked per-worker compression: the residual
    memory is strictly per-worker state, nothing leaks across the batch
    axis (the single-device worker-simulation contract)."""
    cfg = CompressConfig(kind="topk", density=0.06)
    gs = jnp.stack([_payload(200, seed=i) for i in range(N)])
    rs = jnp.stack([_payload(200, seed=10 + i) * 0.1 for i in range(N)])
    sent_v, res_v, d_v = jax.vmap(
        lambda g, r: compress_bucket(cfg, g, r))(gs, rs)
    for i in range(N):
        s_i, r_i, d_i = compress_bucket(cfg, gs[i], rs[i])
        np.testing.assert_array_equal(np.asarray(sent_v[i]), np.asarray(s_i))
        np.testing.assert_array_equal(np.asarray(res_v[i]), np.asarray(r_i))
        np.testing.assert_array_equal(np.asarray(d_v[i]), np.asarray(d_i))


# ---------------------------------------------------------------------------
# GradSync integration: plans, schemes, residual threading
# ---------------------------------------------------------------------------

def _tree_shapes(n_dense=24, dense_size=256, rows=256, d=8):
    return {
        "embed": {"table": jax.ShapeDtypeStruct((rows, d), jnp.float32)},
        "layers": {f"w{i:02d}": jax.ShapeDtypeStruct((dense_size,),
                                                     jnp.float32)
                   for i in range(n_dense)},
    }


def _tree_grads(shapes, density=0.1, seed=0):
    key = jax.random.PRNGKey(seed)

    def leaf(path, s):
        k = jax.random.fold_in(key, hash(bk.leaf_path_str(path)) % (1 << 30))
        g = jax.random.normal(k, (N, *s.shape))
        if "table" in bk.leaf_path_str(path):
            m = jax.random.uniform(k, (N, s.shape[0], 1)) < density
            g = g * m
        return g.astype(s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _make_gs(compress, scheme="auto", bucket_bytes=4096, n=N, shapes=None):
    return GradSync(
        SyncConfig(scheme=scheme, density_budget=0.25,
                   bucket_bytes=bucket_bytes, compress=compress),
        ["embed/table"], shapes or _tree_shapes(), n, data_axis="data")


def _vsync(gs, grads, residual):
    resb = {k: jnp.tile(v[None], (N,) + (1,) * v.ndim)
            for k, v in residual.items()}
    return jax.vmap(lambda g, r: gs(g, r, step=jnp.int32(0)),
                    axis_name="data")(grads, resb)


def test_plan_tags_compressed_dense_buckets_only():
    gs = _make_gs("topk:0.01")
    kinds = {(b.kind, b.compress) for b in gs.plan.buckets}
    for b in gs.plan.buckets:
        if b.kind == bk.SPARSE:
            assert b.compress == "none"
        else:
            assert b.compress == "topk:0.01"
    assert (bk.SPARSE, "none") in kinds
    gs.plan.validate()


def test_auto_flips_on_configured_density():
    """The offline decision: low keep-density -> zen, high -> dense (per
    compressed bucket, from compress_profile through choose_scheme)."""
    lo = _make_gs("topk:0.05", n=2)
    hi = _make_gs("topk:0.5", n=2)
    assert set(lo.bucket_schemes().values()) == {"zen"}
    assert set(hi.bucket_schemes().values()) == {"dense"}


def test_compressed_zen_equals_compressed_dense():
    """The wire scheme must not change WHAT is synchronized: zen on the
    sparsified payloads == psum of the sparsified payloads (Zen's
    no-information-loss claim, now on induced sparsity), and the EF
    residuals — computed before the wire — are bit-identical."""
    shapes = _tree_shapes()
    grads = _tree_grads(shapes)
    out = {}
    for scheme in ("zen", "dense"):
        gs = _make_gs("topk:0.02", scheme=scheme, shapes=shapes)
        synced, nres, stats = _vsync(gs, grads, gs.init_residual())
        out[scheme] = (synced, nres, stats)
    for a, b in zip(jax.tree.leaves(out["zen"][0]),
                    jax.tree.leaves(out["dense"][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    for a, b in zip(jax.tree.leaves(out["zen"][1]),
                    jax.tree.leaves(out["dense"][1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(out["zen"][2]["sync/overflow"]).sum()) == 0


def test_compressed_wire_volume_beats_dense():
    """topk:0.01 + zen must cut the dense buckets' wire volume by >=10x
    (the BENCH acceptance bar, asserted at unit level too)."""
    shapes = {"layers": {f"w{i:02d}": jax.ShapeDtypeStruct((1024,),
                                                           jnp.float32)
                         for i in range(16)}}
    gs = _make_gs("topk:0.01", shapes=shapes, bucket_bytes=1 << 14)
    grads = _tree_grads(shapes)
    assert set(gs.bucket_schemes().values()) == {"zen"}
    _, _, stats = _vsync(gs, grads, gs.init_residual())
    total = sum(p.size for p in jax.tree.leaves(shapes))
    dense_words = 2 * (N - 1) / N * total
    sent = float(np.asarray(stats["sync/sparse_sent_words"]).mean())
    assert float(np.asarray(stats["sync/dense_words"]).mean()) == 0.0
    assert sent < 0.10 * dense_words, (sent, dense_words)


def test_ef_requires_residual():
    gs = _make_gs("topk:0.01")
    with pytest.raises(ValueError, match="residual"):
        jax.vmap(gs, axis_name="data")(_tree_grads(_tree_shapes()))


def test_noef_keeps_no_state():
    gs = _make_gs("topk:0.01:noef")
    assert gs.init_residual() == {}
    synced, nres, stats = _vsync(gs, _tree_grads(_tree_shapes()), {})
    assert nres == {}
    assert gs.plan.counts()["compressed_buckets"] > 0
    assert "sync/compressed_buckets" not in stats


def test_density_metrics_reported():
    gs = _make_gs("topk:0.02")
    _, _, stats = _vsync(gs, _tree_grads(_tree_shapes()), gs.init_residual())
    keys = [k for k in stats if k.startswith("sync/ef_density1")]
    keysN = [k for k in stats if k.startswith("sync/ef_densityN")]
    assert len(keys) == len(keysN) == len(gs.compressed_buckets())
    for k in keys:
        d1 = float(np.asarray(stats[k]).mean())
        assert 0 < d1 <= 0.05  # ~the configured keep-density
    for k in keysN:
        dn = float(np.asarray(stats[k]).mean())
        assert 0 < dn <= 4 * 0.05  # <= n * d1 by the union bound


# ---------------------------------------------------------------------------
# checkpoint round-trip (residual in optimizer state)
# ---------------------------------------------------------------------------

def test_residual_checkpoint_roundtrip(tmp_path):
    """One sync step's residual state survives save/restore through
    checkpoint/io.py bit-exactly, and a restarted trainer continues
    bit-identically to an uninterrupted one."""
    shapes = _tree_shapes(n_dense=8)
    grads = _tree_grads(shapes)
    gs = _make_gs("topk:0.05", shapes=shapes)
    _, res1, _ = _vsync(gs, grads, gs.init_residual())
    state = {"residual": res1, "step": jnp.int32(1)}
    ckpt_io.save(tmp_path / "ck", state)
    back = ckpt_io.restore(tmp_path / "ck")
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # continuing from the restored residual == continuing in-process
    res1_local = {k: v[0] for k, v in res1.items()}
    back_local = {k: v[0] for k, v in back["residual"].items()}
    grads2 = _tree_grads(shapes, seed=1)
    _, r_a, _ = _vsync(gs, grads2, res1_local)
    _, r_b, _ = _vsync(gs, grads2, back_local)
    for a, b in zip(jax.tree.leaves(r_a), jax.tree.leaves(r_b)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_opt_state_carries_residual():
    """steps.init_opt_state / opt_pspecs / abstract_opt_state agree on the
    residual entry: per-device f32, dim0 = devices * local payload."""
    from jax.sharding import PartitionSpec as P

    from repro.models.common import ShardCtx
    from repro.train import steps as st
    from repro.train.steps import TrainerConfig

    ctx = ShardCtx(tp=1, dp=1)
    tcfg = TrainerConfig(sync=SyncConfig(scheme="auto", compress="topk:0.1",
                                         bucket_bytes=4096))
    shapes = _tree_shapes(n_dense=4)
    gs = GradSync(tcfg.sync, ["embed/table"], shapes, 1, data_axis="data")
    params = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)
    specs = jax.tree.map(lambda s: P(*([None] * len(s.shape))), shapes)
    opt = st.init_opt_state(tcfg, params, ctx, specs, gradsync=gs)
    pspecs = st.opt_pspecs(tcfg, specs, ctx, gradsync=gs)
    abstract = st.abstract_opt_state(tcfg, shapes, ctx, specs, gradsync=gs)
    want = gs.compressed_buckets()
    assert set(opt["residual"]) == set(pspecs["residual"]) \
        == set(abstract["residual"]) == set(want)
    for k, size in want.items():
        assert opt["residual"][k].shape == (size,)  # 1 device total
        assert opt["residual"][k].dtype == jnp.float32
        assert abstract["residual"][k].shape == (size,)


# ---------------------------------------------------------------------------
# convergence: the quadratic where plain top-k stalls and EF does not
# ---------------------------------------------------------------------------

def _quadratic_run(ef: bool, steps=200, lr=0.1):
    """2 workers, f_i(x) = ||x - c_i||^2 / 2 with c_i = [+-1, 0.25].

    True optimum x* = mean(c_i) = [0, 0.25].  Per-worker top-1 always
    picks coordinate 0 at x = 0 (|x0 -+ 1| = 1 > 0.25), and the two
    workers' coordinate-0 gradients CANCEL in the mean — so without
    error feedback the iterate never moves: an exact stall.  With EF the
    dropped coordinate-1 signal accumulates in the residual until it
    outweighs coordinate 0, gets transmitted in a burst, and the iterate
    oscillates around the optimum (constant-step EF limit-cycles; its
    Cesàro/tail average is what converges — that is what we assert).

    Returns (final iterate, tail-averaged iterate).
    """
    c = jnp.array([[1.0, 0.25], [-1.0, 0.25]])
    spec = "topk:0.5" + ("" if ef else ":noef")  # k = 1 of 2
    gs = GradSync(
        SyncConfig(scheme="dense", compress=spec),
        [], {"x": jax.ShapeDtypeStruct((2,), jnp.float32)}, 2,
        data_axis="data")
    res = gs.init_residual()
    resb = {k: jnp.zeros((2,) + v.shape, v.dtype) for k, v in res.items()}

    @jax.jit
    def sync(g, r, t):
        return jax.vmap(lambda gg, rr: gs({"x": gg}, rr, step=t),
                        axis_name="data")(g, r)

    x = jnp.zeros(2)
    tail = []
    for t in range(steps):
        g = x[None, :] - c                     # per-worker gradients [2, 2]
        synced, resb, _ = sync(g, resb, jnp.int32(t))
        x = x - lr * synced["x"][0]
        if t >= steps // 2:
            tail.append(np.asarray(x))
    return np.asarray(x), np.mean(tail, axis=0)


def test_topk_with_ef_converges_where_plain_topk_stalls():
    x_plain, avg_plain = _quadratic_run(ef=False)
    _, avg_ef = _quadratic_run(ef=True)
    opt = np.array([0.0, 0.25])
    # plain top-k: worker cancellation -> exact stall at the origin
    np.testing.assert_array_equal(x_plain, np.zeros(2))
    np.testing.assert_array_equal(avg_plain, np.zeros(2))
    # EF: the residual eventually transmits coordinate 1 -> convergence
    assert np.linalg.norm(avg_ef - opt) < 0.06, avg_ef
    assert np.linalg.norm(avg_ef - opt) < 0.2 * np.linalg.norm(
        avg_plain - opt)


# ---------------------------------------------------------------------------
# adaptive density control
# ---------------------------------------------------------------------------

def _stats_for(key, d1, dn):
    return {sparsify.DENSITY1_KEY.format(key=key): d1,
            sparsify.DENSITYN_KEY.format(key=key): dn}


def test_controller_flips_zen_to_dense_on_densification():
    ctl = DensityController({"a": 1 << 14}, {"a": "zen"}, n=2, ema=0.0)
    assert not ctl.drifted()            # no observations: keep the plan
    ctl.observe(_stats_for("a", 0.02, 0.04))
    assert not ctl.drifted()            # sparse: zen stays
    ctl.observe(_stats_for("a", 0.7, 1.0))
    drift = ctl.drifted()
    assert drift == {"a": ("zen", "dense")}
    ctl.rebase({"a": "dense"})
    assert not ctl.drifted()
    # ...and back, when the measured density thins out again
    ctl.observe(_stats_for("a", 0.01, 0.02))
    assert ctl.drifted() == {"a": ("dense", "zen")}


def test_controller_ema_smooths_single_outliers():
    ctl = DensityController({"a": 1 << 14}, {"a": "zen"}, n=2, ema=0.9)
    for _ in range(20):
        ctl.observe(_stats_for("a", 0.02, 0.04))
    ctl.observe(_stats_for("a", 0.9, 1.0))  # one outlier step
    assert not ctl.drifted()                # EMA keeps the plan stable
    for _ in range(40):
        ctl.observe(_stats_for("a", 0.9, 1.0))
    assert ctl.drifted()                    # a sustained shift flips it


def test_controller_profiles_feed_gradsync_replan():
    """The full feedback loop: measured dense-ish profile -> GradSync
    under 'auto' resolves that bucket to dense while an unmeasured one
    keeps zen — per bucket, not globally."""
    shapes = {"layers": {"w00": jax.ShapeDtypeStruct((1024,), jnp.float32),
                         "w01": jax.ShapeDtypeStruct((1024,), jnp.float32)}}
    gs0 = _make_gs("topk:0.05", shapes=shapes, n=2, bucket_bytes=4096)
    assert set(gs0.bucket_schemes().values()) == {"zen"}
    ctl = DensityController(gs0.compressed_buckets(), gs0.bucket_schemes(),
                            n=2, ema=0.0)
    key0 = next(iter(gs0.compressed_buckets()))
    ctl.observe(_stats_for(key0, 0.7, 1.0))
    assert ctl.drifted()
    gs1 = GradSync(
        SyncConfig(scheme="auto", density_budget=0.25, bucket_bytes=4096,
                   compress="topk:0.05"),
        [], shapes, 2, data_axis="data", profiles=ctl.profiles())
    schemes1 = gs1.bucket_schemes()
    assert schemes1[key0] == "dense"
    others = {k: v for k, v in schemes1.items() if k != key0}
    assert others and set(others.values()) == {"zen"}
    # bucket identity is stable across the replan: same keys, same sizes
    assert gs1.compressed_buckets() == gs0.compressed_buckets()
