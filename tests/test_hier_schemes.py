"""Hierarchical synchronization (DESIGN.md §10): hier_sync correctness,
GradSync topology invariance, boundary capacity semantics, the schedule's
intra fence, and collective-free axis sizing.

The §10 hard contracts:
  * hierarchical dense == flat dense BITWISE (psum associativity; grads
    here are dyadic so accumulation order cannot perturb bits);
  * hierarchical zen (and every lossless plan) == the psum oracle;
  * the degenerate topology (node_size=1) is bit-identical to a GradSync
    built with no topology at all — plan tags, outputs, and stats;
  * stage capacities grow across the intra boundary (worst-case merged
    density), so a no-overlap worst case stays overflow-free.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import metrics, schemes
from repro.core import topology as tp
from repro.core.zen import GradSync, SyncConfig

N = 8
M = 2048


def _dyadic_workers(seed, n, m, density, d=None):
    """Sparse worker grads with dyadic values: any summation order is
    exact, so cross-topology comparisons can be bitwise."""
    key = jax.random.PRNGKey(seed)
    masks = metrics.synth_sparse_masks(key, n, m, density)
    vals = jax.random.normal(key, (n, m) if d is None else (n, m, d))
    vals = jnp.round(vals * 256) / 256
    return vals * (masks if d is None else masks[..., None])


def _hier(vals, plan, topo, stage_kw=None):
    return schemes.simulate_hier(vals, topology=topo, plan=plan,
                                 stage_kw=stage_kw)


@pytest.mark.parametrize("node_size", [2, 4, 8])
def test_hier_dense_bitwise_equals_flat_dense(node_size):
    vals = _dyadic_workers(0, N, M, 0.1)
    topo = tp.build_topology(N, node_size)
    out_h, st = _hier(vals, tp.hier_plan("dense", "dense"), topo)
    out_f, _ = schemes.simulate(schemes.dense_sync, vals)
    np.testing.assert_array_equal(np.asarray(out_h), np.asarray(out_f))
    assert len(st.by_level) == 2
    # wire accounting: ring volume per level (inter level free at ns=8)
    ni, ne = topo.intra.size, topo.inter.size
    want_intra = 2 * (ni - 1) / ni * M
    want_inter = 2 * (ne - 1) / ne * M if ne > 1 else 0.0
    np.testing.assert_allclose(
        np.asarray(st.by_level[0]).reshape(-1)[0], want_intra)
    np.testing.assert_allclose(
        np.asarray(st.by_level[1]).reshape(-1)[0], want_inter)


@pytest.mark.parametrize("plan_tag", [
    "hier(zen@intra,zen@inter)",
    "hier(zen@intra,agsparse@inter)",
    "hier(dense@intra,sparcml@inter)",
    "hier(agsparse@intra,dense@inter)",
    "hier(zen@intra,dense@inter)",       # densify-after-intra
])
@pytest.mark.parametrize("node_size", [2, 4])
def test_hier_plans_match_oracle(plan_tag, node_size):
    vals = _dyadic_workers(1, N, M, 0.05)
    oracle = vals.sum(0)
    topo = tp.build_topology(N, node_size)
    plan = tp.parse_plan(plan_tag)
    # provisioning routed through the shared StageArgs builder — capacity
    # growth across the intra merge and zen layout sizing live in ONE
    # place (schemes.plan_stage_args), not re-derived per test harness
    stage_kw = schemes.plan_stage_args(plan, topo, M, density_budget=0.3)
    out, st = _hier(vals, plan, topo, stage_kw)
    assert int(np.asarray(st.overflow).sum()) == 0
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(oracle)[None].repeat(N, 0),
                               atol=1e-4)


def test_capacity_grows_at_intra_boundary():
    """Worst case for the merge: DISJOINT worker supports, so the
    intra-aggregated tensor is n_intra x denser than any worker.  An
    inter stage provisioned with the per-worker budget would overflow;
    the grown budget must not."""
    node_size = 4
    per = M // (2 * N)     # per-worker density 1/16 -> merged 1/4
    vals = np.zeros((N, M), np.float32)
    for i in range(N):
        vals[i, i * per:(i + 1) * per] = 1.0
    vals = jnp.asarray(vals)
    topo = tp.build_topology(N, node_size)
    budget = per / M * 3            # comfortable PER-WORKER budget
    lo_i = schemes.make_zen_layout(M, node_size, density_budget=budget)
    lo_e_small = schemes.make_zen_layout(M, N // node_size,
                                         density_budget=budget)
    lo_e_grown = schemes.make_zen_layout(
        M, N // node_size, density_budget=min(1.0, budget * node_size))
    plan = tp.parse_plan("hier(zen@intra,zen@inter)")
    _, st_bad = _hier(vals, plan, topo,
                      {0: dict(layout=lo_i), 1: dict(layout=lo_e_small)})
    assert int(np.asarray(st_bad.overflow).sum()) > 0
    out, st_ok = _hier(vals, plan, topo,
                       {0: dict(layout=lo_i), 1: dict(layout=lo_e_grown)})
    assert int(np.asarray(st_ok.overflow).sum()) == 0
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(vals.sum(0)),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# GradSync over topologies
# ---------------------------------------------------------------------------

def _shapes():
    return {
        "embed": {"table": jax.ShapeDtypeStruct((256, 8), jnp.float32)},
        "mlp": {"w1": jax.ShapeDtypeStruct((32, 16), jnp.float32),
                "b": jax.ShapeDtypeStruct((7,), jnp.float32)},
    }


def _grads(shapes, density=0.1):
    import zlib

    from repro.core import buckets as bk
    key = jax.random.PRNGKey(0)

    def leaf(path, s):
        name_seed = zlib.crc32(bk.leaf_path_str(path).encode()) % (1 << 30)
        k = jax.random.fold_in(key, name_seed)
        g = jnp.round(jax.random.normal(k, (N, *s.shape)) * 256) / 256
        if "table" in bk.leaf_path_str(path):
            m = metrics.synth_sparse_masks(k, N, s.shape[0], density)
            g = g * m[..., None]
        return g.astype(s.dtype)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def _run_gs(gs, grads):
    topo = gs.topology
    if topo.flat:
        return jax.vmap(gs, axis_name=topo.intra.axis)(grads)
    ni, na = topo.inter.size, topo.intra.size
    gr = jax.tree.map(lambda x: x.reshape(ni, na, *x.shape[1:]), grads)
    out, st = jax.vmap(jax.vmap(gs, axis_name=topo.intra.axis),
                       axis_name=topo.inter.axis)(gr)
    out = jax.tree.map(lambda x: x.reshape(ni * na, *x.shape[2:]), out)
    st = jax.tree.map(lambda x: x.reshape(ni * na, *x.shape[2:]), st)
    return out, st


@pytest.mark.parametrize("scheme", ["zen", "dense", "auto"])
@pytest.mark.parametrize("node_size", [1, 2, 4, 8])
def test_gradsync_values_invariant_across_node_sizes(scheme, node_size):
    """Synced values must be BITWISE identical (dyadic grads) for every
    node grouping of the same 8 workers, for every scheme."""
    shapes = _shapes()
    grads = _grads(shapes)
    cfg = SyncConfig(scheme=scheme, density_budget=0.5, bucket_bytes=1024)
    ref = GradSync(cfg, ["embed/table"], shapes, N, data_axis="data")
    out_ref, st_ref = _run_gs(ref, grads)
    topo = tp.build_topology(N, node_size)
    gs = GradSync(cfg, ["embed/table"], shapes, N, data_axis="data",
                  topology=topo)
    out, st = _run_gs(gs, grads)
    for a, b in zip(jax.tree.leaves(out_ref), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert int(np.asarray(st["sync/overflow"]).sum()) == 0
    if node_size > 1:
        assert "sync/inter_words" in st and "sync/intra_words" in st


def test_degenerate_topology_bit_identical_to_no_topology():
    """node_size=1 IS the pre-refactor stack: same plan tags, same
    outputs, same stats dict, bit for bit."""
    shapes = _shapes()
    grads = _grads(shapes)
    cfg = SyncConfig(scheme="auto", density_budget=0.25, bucket_bytes=512)
    gs0 = GradSync(cfg, ["embed/table"], shapes, N, data_axis="data")
    gs1 = GradSync(cfg, ["embed/table"], shapes, N, data_axis="data",
                   topology=tp.build_topology(N, 1))
    assert [b.scheme for b in gs0.plan.buckets] == \
        [b.scheme for b in gs1.plan.buckets]
    out0, st0 = _run_gs(gs0, grads)
    out1, st1 = _run_gs(gs1, grads)
    for a, b in zip(jax.tree.leaves(out0), jax.tree.leaves(out1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert set(st0) == set(st1)
    for k in st0:
        np.testing.assert_array_equal(np.asarray(st0[k]), np.asarray(st1[k]))


def test_hier_auto_resolves_plan_tags():
    """'auto' on a two-level topology emits CommPlan tags for sparse
    buckets; plain dense buckets keep the 'dense' tag (metric compat)."""
    shapes = _shapes()
    topo = tp.build_topology(N, 4)
    gs = GradSync(SyncConfig(scheme="auto", density_budget=0.01),
                  ["embed/table"], shapes, N, data_axis="data",
                  topology=topo)
    by_name = {b.slots[0].name: b.scheme for b in gs.plan.buckets}
    table_tag = by_name["embed/table"]
    assert table_tag == "dense" or table_tag.startswith("hier("), table_tag
    if table_tag.startswith("hier("):
        tp.parse_plan(table_tag)   # must be grammatical
    assert by_name["mlp/w1"] == "dense"
    # every bucket resolves to an executable two-stage plan
    for line in gs.describe()[1:]:
        assert "plan=[" in line


def test_all_dense_hier_tag_counts_as_dense_words():
    """A plan tag that moves only psum traffic must land in
    sync/dense_words at EVERY node_size — the dense/sparse volume split
    (exact-gated by check_regression) may not change meaning with the
    topology."""
    from repro.core import buckets as bk

    assert bk._all_dense("dense")
    assert bk._all_dense("hier(dense@intra,dense@inter)")
    assert not bk._all_dense("zen")
    assert not bk._all_dense("hier(zen@intra,dense@inter)")
    assert not bk._all_dense("hier(dense@intra,agsparse@inter)")
    assert not bk._all_dense("hier(garbage")

    shapes = {"w": jax.ShapeDtypeStruct((64,), jnp.float32)}
    grads = {"w": jnp.round(
        jax.random.normal(jax.random.PRNGKey(0), (N, 64)) * 256) / 256}
    topo = tp.build_topology(N, 4)
    gs = GradSync(SyncConfig(scheme="dense", bucket_bytes=512),
                  [], shapes, N, data_axis="data", topology=topo)
    _, st = _run_gs(gs, grads)
    assert float(np.asarray(st["sync/sparse_sent_words"]).sum()) == 0.0
    assert float(np.asarray(st["sync/dense_words"]).mean()) > 0.0


def test_gradsync_topology_validation():
    shapes = _shapes()
    with pytest.raises(ValueError, match="workers"):
        GradSync(SyncConfig(), ["embed/table"], shapes, N,
                 data_axis="data", topology=tp.build_topology(4, 2))
    with pytest.raises(ValueError, match="axis"):
        GradSync(SyncConfig(), ["embed/table"], shapes, N,
                 data_axis="data", topology=tp.flat_topology(N, axis="x"))


def test_inter_words_beat_flat_at_low_density():
    """The point of the hierarchy: at low density the slow (inter) links
    carry less than the flat plan pushed across them."""
    vals = _dyadic_workers(3, N, 1 << 14, 0.01)
    layout_f = schemes.make_zen_layout(1 << 14, N, density_budget=0.08)
    _, st_flat = schemes.simulate(schemes.zen_sync, vals, layout=layout_f)
    flat_words = float(np.asarray(st_flat.sent_words).mean())
    topo = tp.build_topology(N, 4)
    lo_i = schemes.make_zen_layout(1 << 14, 4, density_budget=0.08)
    out, st = _hier(vals, tp.parse_plan("hier(zen@intra,agsparse@inter)"),
                    topo, {0: dict(layout=lo_i),
                           1: dict(capacity=1 << 12)})
    inter_words = float(np.asarray(st.by_level[1]).mean())
    assert int(np.asarray(st.overflow).sum()) == 0
    assert inter_words < flat_words, (inter_words, flat_words)
    np.testing.assert_allclose(np.asarray(out[0]),
                               np.asarray(vals.sum(0)), atol=1e-4)


# ---------------------------------------------------------------------------
# schedule fence + axis sizing
# ---------------------------------------------------------------------------

def test_run_schedule_intra_stage_value_identity():
    """The intra hook + its fence are value-identity: the 3-stage
    pipeline returns exactly what calling the stages directly returns."""
    from repro.core.buckets import Bucket, LeafSlot
    from repro.train.schedule import run_schedule

    buckets = [
        Bucket(bid=i, kind="dense_fused", scheme="dense",
               slots=(LeafSlot(f"w{i}", i, (4,), jnp.float32, 0, 4),),
               nbytes=16)
        for i in range(3)
    ]
    payloads = [jnp.arange(4.0) + i for i in range(3)]
    enc_log, intra_log = [], []

    def encode(b, p):
        enc_log.append(b.bid)
        return p * 2

    def intra(b, e):
        intra_log.append(b.bid)
        return e + 1

    def commit(b, e):
        return e * 10, schemes.SyncStats(
            sent_words=jnp.float32(b.bid), overflow=jnp.int32(0))

    outs, stats = run_schedule(buckets, payloads, encode, commit,
                               intra=intra)
    for i in range(3):
        np.testing.assert_allclose(np.asarray(outs[i]),
                                   np.asarray((payloads[i] * 2 + 1) * 10))
    assert enc_log == [0, 1, 2] and intra_log == [0, 1, 2]


def test_axis_size_emits_no_collective():
    """lax.axis_size must resolve statically: a lowered dense_sync
    contains exactly ONE all-reduce (the gradient psum), not a second one
    for the worker count."""
    from jax.sharding import PartitionSpec as P

    n = jax.device_count()
    mesh = jax.make_mesh((n,), ("data",))
    def f(v):
        out, st = schemes.dense_sync(v[0], axis="data")
        return out, st.sent_words

    g = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                      out_specs=(P(), P()), check_vma=False)
    hlo = jax.jit(g).lower(
        jnp.ones((n, 64))).compile().as_text()
    # zenlint's parsed-HLO counter: async start/done pairs count once
    from repro.analysis import hlo_ir
    n_ar = hlo_ir.count_collectives(hlo_ir.HloModule.parse(hlo),
                                    base="all-reduce")
    assert n_ar == 1, f"expected 1 all-reduce (the psum), found {n_ar}"
