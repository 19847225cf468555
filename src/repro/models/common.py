"""Model-zoo foundations: architecture config, shard context, param utilities.

Everything model-side runs *inside* ``shard_map`` (Megatron-style explicit
tensor parallelism) so gradient synchronization — the paper's subject — is an
explicit, schedulable operation rather than a compiler insertion.  Parameters
are global arrays with a mirrored ``PartitionSpec`` tree; inside the shard_map
region each leaf is its local shard.

Sharding rules (DESIGN.md §5):
  * MLP / expert / SSM inner dims: column→row parallel over ``model``
    (always divisible for the assigned zoo).
  * Attention q-heads: sharded over ``model`` iff ``n_heads % tp == 0``,
    else replicated (qwen2-0.5b 14H, phi4-mini 24H, minicpm3 40H).
  * KV projections: always replicated (kv-head counts are small and rarely
    divide tp; the FLOP share is negligible).
  * Embedding / LM head: vocab-sharded over ``model`` (vocab padded to a
    multiple of 128 — standard practice; padded ids are never produced).
  * Decode KV cache: sequence-sharded over ``model`` (round-robin slots) —
    works for any head count and divides cache HBM by tp.
"""
from __future__ import annotations

import dataclasses
import math
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

# Vocab is always padded to a multiple of this, independent of the mesh
# (DESIGN.md §9): parameter SHAPES must never depend on tp.  make_ctx
# asserts vocab_padded % tp == 0 instead of growing the pad.
VOCAB_PAD = 128


def pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Yarn:
    """YaRN rope scaling (arXiv:2309.00071), DeepSeek-V2's form: rope dims
    whose wavelength fits ``beta_fast`` turns in ``original`` positions keep
    their frequency, those with under ``beta_slow`` turns are divided by
    ``factor``, a linear ramp lies between; the softmax scale gains
    ``(0.1 * mscale_all_dim * ln(factor) + 1) ** 2``."""

    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One assigned architecture (exact sizes from the assignment block)."""

    name: str
    kind: str                  # dense | moe | ssm | hybrid | enc_dec | vlm
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 0          # 0 -> d_model // n_heads
    qkv_bias: bool = False
    rope_theta: float = 10_000.0
    # --- MoE ---
    n_experts: int = 0             # the router's width: experts in the model
    top_k: int = 0
    capacity_factor: float = 1.25  # moe_a2a's static exchange buffers only
    d_ff_expert: int = 0           # expert (and shared-expert) width; 0 -> d_ff
    n_shared_experts: int = 0      # one SwiGLU of n_shared x d_ff_expert, every token
    first_k_dense: int = 0         # leading dense SwiGLU layers (d_ff) before the MoE stack
    experts_held: int = 0          # experts [0, held) live here; 0 -> all n_experts
    norm_topk: bool = True         # renormalise the top-k gates to sum to 1
    aux_alpha: float = 0.01        # weight of the balance loss
    seq_aux: bool = False          # balance loss per sequence, summed over MoE
    #                                layers (DeepSeek-V2); else Switch-style
    #                                over the batch, averaged over layers
    # --- SSM (mamba2 / hybrid) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_chunk: int = 64
    # --- hybrid (zamba2-style shared attention block) ---
    shared_attn_every: int = 0     # apply shared attn block every k ssm layers
    # --- MLA (minicpm3, deepseek-v2); head_dim is the no-rope q/k dim ---
    mla_q_rank: int = 0            # 0 -> plain q projection (no q compression)
    mla_kv_rank: int = 0           # 0 -> standard GQA
    mla_rope_dim: int = 32
    mla_v_dim: int = 64
    yarn: Optional[Yarn] = None    # rope scaling of the MLA rope dims
    # --- enc-dec (whisper backbone) ---
    n_enc_layers: int = 0
    enc_len: int = 1500            # encoder frames (stub embeddings)
    # --- vlm ---
    n_patches: int = 0             # patch-embedding prefix length (stub)
    # --- long-context ---
    sliding_window: int = 4096     # used by long_500k decode for attn archs
    # --- numerics ---
    norm_eps: float = 1e-5         # RMSNorm epsilon of the decoder blocks
    dtype: Any = jnp.bfloat16
    source: str = ""               # citation

    @property
    def hd(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_attn_free(self) -> bool:
        return self.kind == "ssm"

    @property
    def is_mla(self) -> bool:
        return self.mla_kv_rank > 0

    @property
    def expert_ff(self) -> int:
        return self.d_ff_expert or self.d_ff

    @property
    def held(self) -> int:
        """Routed experts whose weights live here (the router still scores
        all ``n_experts``)."""
        return self.experts_held or self.n_experts

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_heads(self) -> int:
        return self.d_inner // self.ssm_head_dim

    @property
    def vocab_padded(self) -> int:
        """Vocab padded to a fixed multiple of ``VOCAB_PAD`` — deliberately
        NOT a function of the mesh, so the embedding / LM-head shapes (and
        therefore the init key→param mapping) are identical on every mesh.
        ``make_ctx`` asserts divisibility by tp instead."""
        return pad_to(self.vocab, VOCAB_PAD)

    def n_params(self) -> int:
        """Approximate parameter count (for roofline MODEL_FLOPS)."""
        d, L = self.d_model, self.n_layers
        emb = self.vocab * d
        if self.kind == "ssm":
            din = self.d_inner
            per = (d * (2 * din + 2 * self.ssm_heads + 2 * self.ssm_state)
                   + din * d + din * self.ssm_conv)
            return emb + L * per
        attn = self._attn_params()
        if self.kind == "moe":
            ffn = (self.held + self.n_shared_experts) * 3 * d * self.expert_ff \
                + d * self.n_experts
        else:
            ffn = 3 * d * self.d_ff
        per = attn + ffn
        total = emb + L * per
        if self.kind == "moe" and self.first_k_dense:
            total += self.first_k_dense * (3 * d * self.d_ff - ffn)
        if self.kind == "enc_dec":
            total += self.n_enc_layers * (attn + ffn) + L * attn  # cross-attn
        if self.kind == "hybrid":
            din = self.d_inner
            ssm_per = (d * (2 * din + 2 * self.ssm_heads + 2 * self.ssm_state)
                       + din * d + din * self.ssm_conv)
            total = emb + L * ssm_per + (attn + ffn)  # one shared block
        return total

    def _attn_params(self) -> int:
        d, H = self.d_model, self.n_heads
        if not self.is_mla:
            return d * (H * self.hd) * 2 + d * (self.n_kv * self.hd) * 2
        qk = H * (self.hd + self.mla_rope_dim)
        q = (d * self.mla_q_rank + self.mla_q_rank * qk if self.mla_q_rank
             else d * qk)
        return (q + d * (self.mla_kv_rank + self.mla_rope_dim)
                + self.mla_kv_rank * H * (self.hd + self.mla_v_dim)
                + H * self.mla_v_dim * d)

    def n_active_params(self) -> int:
        """Active params per token (MoE uses top_k of n_experts)."""
        if self.kind != "moe":
            return self.n_params()
        d, L = self.d_model, self.n_layers
        ffn = ((self.top_k + self.n_shared_experts) * 3 * d * self.expert_ff
               + d * self.n_experts)
        return (self.vocab * d + L * (self._attn_params() + ffn)
                + self.first_k_dense * (3 * d * self.d_ff - ffn))

    def reduced(self) -> "ArchConfig":
        """Smoke-test variant: 2 layers, d_model<=256, <=4 experts."""
        return dataclasses.replace(
            self,
            n_layers=2,
            n_enc_layers=min(self.n_enc_layers, 2),
            d_model=256,
            n_heads=4,
            n_kv=min(self.n_kv, 2) if self.n_kv < self.n_heads else 4,
            head_dim=64,
            d_ff=384,
            vocab=512,
            n_experts=min(self.n_experts, 4),
            top_k=min(self.top_k, 2),
            d_ff_expert=min(self.d_ff_expert, 128),
            experts_held=min(self.experts_held, 4),
            first_k_dense=min(self.first_k_dense, 1),
            mla_q_rank=min(self.mla_q_rank, 64),
            mla_kv_rank=min(self.mla_kv_rank, 32),
            enc_len=min(self.enc_len, 24),
            n_patches=min(self.n_patches, 8),
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_chunk=16,
            sliding_window=128,
            shared_attn_every=min(self.shared_attn_every, 1) or self.shared_attn_every,
        )


# ---------------------------------------------------------------------------
# Shard context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh axes + per-family sharding decisions, fixed at build time.

    Mesh-invariance contract (DESIGN.md §9): every layer computes *global*
    semantics — the mesh only chooses the layout.  Concretely:

      * global parameter shapes, dtypes, and pytree paths are identical for
        every (tp, dp, pods) — only the ``PartitionSpec`` trees differ
        (``assert_mesh_invariant_params`` enforces this on every build);
      * each leaf's init key is a pure function of (root key, leaf path)
        — see ``ParamBuilder`` — and random bits are sharding-invariant
        (``jax_threefry_partitionable``, enabled in ``repro/__init__``),
        so ``same key -> bitwise-same global param pytree`` on any mesh;
      * forward math is the same global function on every mesh: sharded
        reductions (psum / pmax over ``model``) reconstruct exactly the
        full-dim quantity, never a per-shard approximation;
      * divisibility preconditions are validated eagerly by ``make_ctx``
        with errors naming the config, never absorbed by growing shapes.

    The one documented exception is ``h_pad`` (opt-in ``pad_heads=True``):
    padding q-heads up to a tp multiple changes global shapes by design,
    trading bit-parity across meshes for shardability.
    """

    tp: int                        # model-axis size
    dp: int                        # data-axis size
    pods: int = 1
    tp_axis: str = "model"
    dp_axis: str = "data"
    pod_axis: Optional[str] = None
    # Hierarchical data parallelism (DESIGN.md §10): devices per node.
    # node_size > 1 splits the data axis into nested mesh axes
    # ("dp_inter", "dp_intra") — see ``dp_axes`` — so the sync stack can
    # aggregate within a node before crossing the slow inter-node links.
    # node_size == 1 keeps the single historical "data" axis: every
    # consumer sees exactly the pre-topology axis names and sizes.
    node_size: int = 1
    shard_heads: bool = True       # q-heads over tp (set from cfg)
    decode_seq_shard: bool = True  # KV cache sequence-sharded over tp
    # §Perf optimization: pad q-heads up to a tp multiple so attention can
    # shard instead of replicating (qwen2 14->16, phi4 24->32, minicpm3
    # 40->48).  Padded heads are zero-initialized: the function at init is
    # exactly the spec architecture; under training they become (tiny)
    # extra capacity — the standard Megatron-style padding trade-off.
    h_pad: int = 0                 # 0 = no padding; else the padded H
    # §Perf optimization: token-sharded MoE dispatch over the model axis
    # (two all-to-alls instead of a full-activation psum) — see moe.py
    moe_a2a: bool = False

    @property
    def dp_axes(self) -> tuple:
        """Mesh axes spanning the data-parallel world, outermost first:
        the single ``dp_axis`` when flat, ``(dp_inter, dp_intra)`` when
        node-split.  Collectives that must cover ALL data ranks (ZeRO
        gathers, metric pmeans) take this tuple as their axis name."""
        if self.node_size > 1:
            from repro.core.topology import DP_INTER, DP_INTRA
            return (DP_INTER, DP_INTRA)
        return (self.dp_axis,)

    @property
    def batch_axes(self):
        head = (self.pod_axis,) if self.pod_axis else ()
        return head + self.dp_axes

    @property
    def axis_sizes(self) -> dict:
        """{mesh axis name: size} for every axis this ctx shards over —
        the one table spec-divisor math should consult (steps.py)."""
        sizes = {self.tp_axis: self.tp}
        if self.node_size > 1:
            from repro.core.topology import DP_INTER, DP_INTRA
            sizes[DP_INTER] = self.dp // self.node_size
            sizes[DP_INTRA] = self.node_size
        else:
            sizes[self.dp_axis] = self.dp
        if self.pod_axis:
            sizes[self.pod_axis] = self.pods
        return sizes

    def tp_rank(self):
        return lax.axis_index(self.tp_axis)

    def psum_tp(self, x):
        return lax.psum(x, self.tp_axis) if self.tp > 1 else x

    def pmax_tp(self, x):
        """Cross-rank max, treated as a constant under differentiation
        (used only for numerical-stability shifts; ``pmax`` has no JVP rule).
        """
        if self.tp == 1:
            return x

        @jax.custom_jvp
        def f(y):
            return lax.pmax(y, self.tp_axis)

        @f.defjvp
        def _jvp(primals, tangents):
            (y,) = primals
            return f(y), jnp.zeros_like(y)

        return f(x)


def _require(cond: bool, cfg: ArchConfig, why: str) -> None:
    if not cond:
        raise ValueError(f"config '{cfg.name}': {why}")


def validate_tp(cfg: ArchConfig, tp: int, *, shard_heads: bool,
                h_pad: int) -> None:
    """Eager divisibility checks for a tensor-parallel degree.

    Shapes are mesh-invariant by contract (DESIGN.md §9), so a tp that does
    not divide them is a configuration error — reported here, at
    ``make_ctx`` time, with the config named, instead of surfacing as a
    bare assert deep inside a layer init."""
    if tp <= 1:
        return
    vp = cfg.vocab_padded
    _require(vp % tp == 0, cfg,
             f"padded vocab {vp} (vocab {cfg.vocab} padded to a fixed "
             f"multiple of {VOCAB_PAD}, mesh-invariant) is not divisible "
             f"by tp={tp}; pick a tp dividing {vp}")
    uses_mlp = (cfg.kind in ("dense", "enc_dec", "vlm", "hybrid")
                or bool(cfg.mla_q_rank) or bool(cfg.first_k_dense))
    if uses_mlp:
        _require(cfg.d_ff % tp == 0, cfg,
                 f"d_ff={cfg.d_ff} is not divisible by tp={tp} "
                 f"(MLP is column->row parallel over the model axis)")
    if cfg.kind == "moe":
        _require(cfg.held % tp == 0, cfg,
                 f"the {cfg.held} experts held (n_experts={cfg.n_experts}) "
                 f"are not divisible by tp={tp} (experts are sharded over "
                 f"the model axis)")
        shared = cfg.n_shared_experts * cfg.expert_ff
        _require(shared % tp == 0, cfg,
                 f"shared-expert width {shared} is not divisible by tp={tp}")
    if cfg.kind in ("ssm", "hybrid"):
        _require(cfg.d_inner % tp == 0, cfg,
                 f"d_inner={cfg.d_inner} is not divisible by tp={tp}")
        _require(cfg.ssm_heads % tp == 0, cfg,
                 f"ssm_heads={cfg.ssm_heads} is not divisible by tp={tp}")
    # GQA head/KV nesting (MLA broadcasts k_rope per-head instead of
    # slicing replicated KV heads, so the nesting constraint is GQA-only)
    if (shard_heads and cfg.n_heads and not cfg.is_attn_free
            and not cfg.is_mla):
        H = h_pad or cfg.n_heads
        _require(H % cfg.n_kv == 0, cfg,
                 f"n_heads={H} is not a multiple of n_kv={cfg.n_kv}")
        Hl, g = H // tp, H // cfg.n_kv
        _require(Hl % g == 0 or g % Hl == 0, cfg,
                 f"local q-heads {Hl} and GQA group {g} do not nest at "
                 f"tp={tp} (need Hl % g == 0 or g % Hl == 0 for the "
                 f"replicated-KV slice)")


def make_ctx(cfg: ArchConfig, tp: int, dp: int, pods: int = 1,
             pad_heads: bool = False, moe_a2a: bool = False,
             node_size: int = 1) -> ShardCtx:
    h_pad = 0
    shard = cfg.n_heads % tp == 0
    if pad_heads and not shard and cfg.n_heads > 0:
        h_pad = pad_to(cfg.n_heads, tp)
        shard = True
    validate_tp(cfg, tp, shard_heads=shard, h_pad=h_pad)
    if moe_a2a and tp > 1:
        _require(cfg.held == cfg.n_experts, cfg,
                 "moe_a2a exchanges tokens with the owners of all "
                 "n_experts; it cannot run with a share of them held")
    if node_size > 1:
        _require(dp % node_size == 0, cfg,
                 f"node_size={node_size} does not divide the data-parallel "
                 f"degree dp={dp}; pick a node size dividing {dp} (or 1 "
                 f"for the flat topology)")
    return ShardCtx(
        tp=tp, dp=dp, pods=pods,
        pod_axis="pod" if pods > 1 else None,
        shard_heads=shard,
        h_pad=h_pad,
        moe_a2a=moe_a2a,
        node_size=max(node_size, 1),
    )


# ---------------------------------------------------------------------------
# Parameter initialization helpers (global arrays + mirrored PartitionSpecs)
# ---------------------------------------------------------------------------

def path_key(key: jax.Array, token: str | int) -> jax.Array:
    """Derive a child PRNG key from one path component.

    The key of every parameter leaf is a pure function of (root key, leaf
    path) — NOT of the order or number of sibling ``dense``/``child`` calls
    — so key assignment is provably independent of the mesh and of any
    layout decision an init function makes (DESIGN.md §9).  String
    components are folded in via a stable 31-bit CRC; integer components
    (stacked-layer indices) fold in directly and cannot collide with
    strings in practice because stacked layers live in their own
    name-derived subtree.
    """
    if isinstance(token, int):
        return jax.random.fold_in(key, token)
    return jax.random.fold_in(key, zlib.crc32(token.encode()) & 0x7FFFFFFF)


class ParamBuilder:
    """Collects (value, spec) pairs into mirrored pytrees.

    ``abstract=True`` records ``jax.ShapeDtypeStruct`` leaves instead of
    materializing arrays — used by the dry-run and by spec-tree construction
    (no allocation, no RNG).

    Key discipline: each leaf draws from ``path_key(subtree_key, name)``.
    There is no sequential key consumption, so two builds of the same
    architecture assign identical keys to identical paths no matter what
    mesh (or code path ordering) produced them.
    """

    def __init__(self, key: jax.Array | None, dtype=jnp.bfloat16,
                 abstract: bool = False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params: dict = {}
        self.specs: dict = {}

    def key_for(self, name: str | int) -> jax.Array | None:
        if self.abstract:
            return None
        return path_key(self._key, name)

    def _put(self, name, shape, dtype, make):
        if self.abstract:
            self.params[name] = jax.ShapeDtypeStruct(tuple(shape), dtype)
        else:
            self.params[name] = make()

    def dense(self, name: str, shape, spec: P, scale: float | None = None,
              dtype=None):
        fan_in = shape[0] if len(shape) > 1 else shape[-1]
        scale = scale if scale is not None else 1.0 / math.sqrt(fan_in)
        dt = dtype or self.dtype
        self._put(name, shape, dt,
                  lambda: (jax.random.normal(self.key_for(name), shape,
                                             jnp.float32) * scale).astype(dt))
        self.specs[name] = spec

    def zeros(self, name: str, shape, spec: P, dtype=None):
        dt = dtype or self.dtype
        self._put(name, shape, dt, lambda: jnp.zeros(shape, dt))
        self.specs[name] = spec

    def ones(self, name: str, shape, spec: P, dtype=None):
        dt = dtype or self.dtype
        self._put(name, shape, dt, lambda: jnp.ones(shape, dt))
        self.specs[name] = spec

    def const(self, name: str, value, spec: P):
        if self.abstract:
            self.params[name] = jax.ShapeDtypeStruct(value.shape, value.dtype)
        else:
            self.params[name] = value
        self.specs[name] = spec

    def child(self, name: str) -> "ParamBuilder":
        sub = ParamBuilder(self.key_for(name), self.dtype, self.abstract)
        self.params[name] = sub.params
        self.specs[name] = sub.specs
        return sub

    def stacked(self, name: str, n: int, init_fn) -> None:
        """Stack ``n`` copies of a sub-module's params along a new leading
        layer axis (for ``lax.scan`` over layers).  Layer ``i`` builds from
        ``path_key(path_key(subtree, name), i)``."""
        if self.abstract:
            b = ParamBuilder(None, self.dtype, abstract=True)
            init_fn(b)
            self.params[name] = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype),
                b.params)
            spec = b.specs
        else:
            base = self.key_for(name)
            subs = []
            spec = None
            for i in range(n):
                b = ParamBuilder(path_key(base, i), self.dtype)
                init_fn(b)
                subs.append(b.params)
                spec = b.specs
            self.params[name] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *subs)

        def lift(s: P) -> P:
            return P(None, *s)

        self.specs[name] = jax.tree.map(
            lift, spec, is_leaf=lambda x: isinstance(x, P))


def zero_rows_from(b: ParamBuilder, name: str, start: int) -> None:
    """Zero leaf ``name``'s rows [start:] (padding rows must not carry
    random init — e.g. embedding vocab padding).  No-op when abstract or
    when there is no padding."""
    w = b.params.get(name)
    if b.abstract or w is None or start >= w.shape[0]:
        return
    b.params[name] = w.at[start:, :].set(0)


def zero_cols_from(b: ParamBuilder, name: str, start: int) -> None:
    """Zero leaf ``name``'s trailing-dim columns [start:]."""
    w = b.params.get(name)
    if b.abstract or w is None or start >= w.shape[-1]:
        return
    b.params[name] = w.at[..., start:].set(0)
