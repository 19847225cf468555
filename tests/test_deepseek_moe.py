"""DeepSeek-V2-Lite's block at a tiny size on the CPU, against the plain
float32 reference (``benchmarks/chip/reference/moe.py``) from the same
seeded weights: MLA without query compression under YaRN, the leading
dense layer, the expert layer at a share of its experts (dropless, with
shared experts and the per-sequence balance loss), and the block map of
the compiled step."""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import check
from benchmarks.chip.reference import common
from benchmarks.chip.reference import moe as ref
from repro.analysis import scopes
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import layers as L
from repro.models import moe as M
from repro.models.common import make_ctx
from repro.models.model import build_model
from repro.train.build import attach_train, build_program

ROOT = Path(__file__).resolve().parents[1]
CONFIG = json.loads(
    (ROOT / "benchmarks/chip/configs/deepseek-v2-lite.json").read_text())
# every width cut; 8 routed experts, 2 of them held: 4 shares
TINY = dict(num_hidden_layers=3, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_attention_heads=4,
            num_key_value_heads=4, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, kv_lora_rank=16, n_routed_experts=8,
            num_experts_per_tok=3, experts_held=2, vocab_size=512)
S, B = 64, 2
F32 = common.make_mm("f32")


def tiny(**over):
    """(reference config dict, program ArchConfig in float32)."""
    c = {**CONFIG, **TINY, **over}
    fields = {f: c[k] for k, f in ref.ARCH_FIELDS.items()}
    cfg = dataclasses.replace(get_config(c["arch"]), **fields, dtype=jnp.float32)
    return c, cfg


def weights(c, model, seed=0):
    """{path: f32 value} of the model's parameters, drawn by the
    reference's rule, and the program's pytree of the same values."""
    shapes = model.abstract()[0]
    paths = check.leaf_paths(shapes)
    leaves = jax.tree_util.tree_leaves(shapes)
    key = jax.random.PRNGKey(seed)
    flat = {p: ref.init_leaf(p, s.shape, check.leaf_key(key, p), c)
            for p, s in zip(paths, leaves)}
    tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(shapes), [flat[p] for p in paths])
    return flat, tree


def batch(c, seed=1):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, c["vocab_size"], (B, S + 1)).astype(np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


@pytest.fixture(scope="module")
def parity():
    c, cfg = tiny()
    model = build_model(cfg, make_ctx(cfg, 1, 1))
    flat, tree = weights(c, model)
    tokens, labels = batch(c)
    (_, metrics), g_prog = jax.jit(jax.value_and_grad(
        model.train_loss, has_aux=True))(tree, {"tokens": tokens, "labels": labels})

    def ref_loss(P, alpha):
        s, n = common.batch_nll(P, tokens, labels, dict(c, aux_loss_alpha=alpha),
                                F32, ref.trunk)
        return s / n

    loss_ref, g_ref = jax.jit(jax.value_and_grad(ref_loss))(flat, c["aux_loss_alpha"])
    g_no_aux = jax.jit(jax.grad(ref_loss))(flat, 0.0)
    return dict(c=c, flat=flat, tokens=tokens, metrics=metrics, loss_ref=loss_ref,
                g_prog=check.flat(g_prog), g_ref=g_ref, g_no_aux=g_no_aux)


def test_loss_matches_reference(parity):
    assert float(parity["metrics"]["loss"]) == pytest.approx(
        float(parity["loss_ref"]), rel=1e-5)
    aux = ref.balance_loss(parity["flat"], parity["tokens"], parity["c"], F32)
    assert float(parity["metrics"]["moe/aux_loss"]) == pytest.approx(float(aux), rel=1e-5)
    # 2 MoE layers, 3 picks of 8 experts, 2 held: 2 x (B*S*3*2/8) pairs expected
    assert 0 < float(parity["metrics"]["moe/held_pairs"]) <= 2 * B * S * 2


def test_every_leaf_gradient_matches_reference(parity):
    g_prog, g_ref = parity["g_prog"], parity["g_ref"]
    assert set(g_prog) == set(g_ref)
    assert any(p.startswith("dense_layers/") for p in g_ref)
    worst = max(g_ref, key=lambda p: rel(g_prog[p], g_ref[p]))
    assert rel(g_prog[worst], g_ref[worst]) < 2e-4, worst


def test_balance_loss_reaches_the_router_gradient(parity):
    """The objective's balance term moves the router's gradient by far
    more than the program and the reference differ."""
    p = "layers/ffn/router_w"
    moved = rel(parity["g_no_aux"][p], parity["g_ref"][p])
    assert moved > 50 * rel(parity["g_prog"][p], parity["g_ref"][p])


# ---------------------------------------------------------------------------
# the expert layer alone: shares, and routing skewed onto one expert
# ---------------------------------------------------------------------------

def _layer(c, cfg, seed=3):
    """One MoE layer's program params and reference params, and h [S, d]."""
    model = build_model(cfg, make_ctx(cfg, 1, 1))
    flat, tree = weights(c, model, seed)
    lp = {k: v[0] for k, v in common.layer_params(flat).items()}
    h = jax.random.normal(jax.random.PRNGKey(seed + 1), (S, c["hidden_size"]))
    return jax.tree.map(lambda a: a[0], tree["layers"]), lp, h


def test_shares_sum_to_the_uncut_layer():
    """E = 8 routed experts held 2 at a time: the 4 shares' routed outputs,
    with the shared experts counted once, give the uncut reference layer."""
    c, cfg = tiny(num_hidden_layers=1, first_k_dense_replace=0, experts_held=8)
    p_full, lp, h = _layer(c, cfg)
    want, _ = ref.moe_ffn(h, lp, c, F32)
    _, share_cfg = tiny(num_hidden_layers=1, first_k_dense_replace=0)
    ctx = make_ctx(share_cfg, 1, 1)
    x = h[None]
    got = L.swiglu(p_full["ffn"], "shared", x, ctx)[0]
    held = share_cfg.held
    for j in range(c["n_routed_experts"] // held):
        ffn = dict(p_full["ffn"],
                   router_w=jnp.roll(p_full["ffn"]["router_w"], -j * held, axis=1),
                   **{w: p_full["ffn"][w][j * held:(j + 1) * held]
                      for w in ("w_gate", "w_up", "w_down")})
        y, _ = M.moe_ffn_dropless({"ffn": ffn}, "ffn", x, share_cfg, ctx)
        got = got + y[0]
    assert rel(got, want) < 1e-5


def test_dropless_when_every_token_picks_the_held_experts():
    """A router that sends every token to experts 0 and 1 (both held):
    every one of the 2 x T pairs is computed, where a capacity of 1.25x
    the mean load would drop most of them."""
    c, cfg = tiny(num_hidden_layers=1, first_k_dense_replace=0)
    p, lp, h = _layer(c, cfg)
    h = jnp.abs(h)
    router = jnp.zeros_like(p["ffn"]["router_w"]).at[:, 0].set(5.0)
    p = dict(p, ffn=dict(p["ffn"], router_w=router))
    lp = dict(lp, **{"ffn/router_w": router})
    want, _ = ref.moe_ffn(h, lp, c, F32)
    ctx = make_ctx(cfg, 1, 1)
    y, stats = M.moe_ffn(p, "ffn", h[None], cfg, ctx)
    assert float(stats["moe/held_pairs"]) == 2 * S
    assert 2 * S > math.ceil(S * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    assert rel(y[0], want) < 1e-5


# ---------------------------------------------------------------------------
# MLA and YaRN at the published sizes
# ---------------------------------------------------------------------------

def test_yarn_at_the_published_sizes():
    cfg = get_config("deepseek-v2-lite")
    freqs, gain = L.yarn_freqs(cfg.mla_rope_dim, cfg.rope_theta, cfg.yarn)
    base = cfg.rope_theta ** (-np.arange(32) / 32)
    np.testing.assert_allclose(freqs[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], base[23:] / 40, rtol=1e-6)
    assert np.all((freqs[11:23] < base[11:23]) & (freqs[11:23] > base[11:23] / 40))
    assert gain == 1.0
    from repro.models.attention import mla_scale
    assert mla_scale(cfg) == pytest.approx(0.1147, abs=1e-4)
    inv, rgain, rscale = ref.yarn(CONFIG)
    np.testing.assert_allclose(np.asarray(inv), freqs, rtol=1e-6)
    assert (rgain, rscale) == pytest.approx((gain, mla_scale(cfg)))
    assert cfg.yarn.factor == CONFIG["rope_scaling"]["factor"]
    assert cfg.yarn.original == CONFIG["rope_scaling"]["original_max_position_embeddings"]


@pytest.mark.parametrize("arch,q_leaves", [
    ("deepseek-v2-lite", {"q_w"}),
    ("minicpm3-4b", {"q_down_w", "q_up_w", "q_norm"}),
])
def test_mla_query_projection_follows_the_q_rank(arch, q_leaves):
    cfg = get_config(arch).reduced()
    model = build_model(cfg, make_ctx(cfg, 1, 1))
    attn = set(model.abstract()[0]["layers"]["attn"])
    assert {k for k in attn if k.startswith("q")} == q_leaves


# ---------------------------------------------------------------------------
# the block map of the compiled step
# ---------------------------------------------------------------------------

def test_step_blocks_map_every_instruction():
    cfg = get_config("deepseek-v2-lite").reduced()
    prog = build_program(cfg, make_mesh((1, 1), ("data", "model")))
    attach_train(prog, seq_len=32, global_batch=2)
    blocks, layers = prog.step_blocks(), prog.step_scopes()
    assert set(blocks) == set(layers)
    assert set(blocks.values()) == {scopes.MOE_ROUTE, scopes.MOE_EXPERTS, scopes.OTHER}
    # the grouped matmuls count as experts in the forward, the recomputed
    # forward and the backward alike
    assert {layers[i] for i, b in blocks.items() if b == scopes.MOE_EXPERTS} >= {
        "fwd", "remat", "bwd"}
    assert set(layers.values()) <= set(scopes.ALL)


# ---------------------------------------------------------------------------
# the balance loss's form follows the configuration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch,want", [
    ("deepseek-v2-lite", 2.8),   # per sequence: each row 4/4 * 4 * 0.7
    ("olmoe-1b-7b", 1.6),        # Switch over the batch: 4 * (0.5*0.4 + 0.5*0.4)
    ("phi3.5-moe-42b-a6.6b", 1.6),
])
def test_balance_loss_form_follows_the_config(arch, want):
    """Two rows of 4 tokens, 4 experts, one pick each: row 0 sends every
    token to expert 0, row 1 to expert 1, with probability 0.7."""
    cfg = get_config(arch)
    probs = jnp.asarray([[0.7, 0.1, 0.1, 0.1]] * 4 + [[0.1, 0.7, 0.1, 0.1]] * 4)
    eidx = jnp.asarray([[0]] * 4 + [[1]] * 4)
    aux, _ = M.balance_loss(probs, eidx, 2, cfg)
    assert float(aux) == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("seq_aux,layers_want", [(False, 1.0), (True, 3.0)])
def test_balance_loss_over_layers_and_its_weight(seq_aux, layers_want):
    """OLMoE's form (Switch, averaged over layers, weight 0.01) against
    DeepSeek's (summed over layers): with the router's weights zero every
    layer's loss is 1 in either form, so 3 layers read 1 or 3."""
    cfg = dataclasses.replace(get_config("olmoe-1b-7b").reduced(), n_layers=3,
                              seq_aux=seq_aux, dtype=jnp.float32)
    assert cfg.aux_alpha == 0.01
    model = build_model(cfg, make_ctx(cfg, 1, 1))
    c = {"vocab_size": cfg.vocab}
    _, tree = weights(c, model)
    ffn = tree["layers"]["ffn"]
    tree["layers"]["ffn"] = dict(ffn, router_w=jnp.zeros_like(ffn["router_w"]))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (B, S + 1)), jnp.int32)
    loss, metrics = jax.jit(model.train_loss)(
        tree, {"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    assert float(metrics["moe/aux_loss"]) == pytest.approx(layers_want, rel=1e-6)
    assert float(loss - metrics["loss"]) == pytest.approx(0.01 * layers_want, rel=1e-4)
