"""Distributed train / serve steps (per-device SPMD programs + shard_map
wrappers).

Gradient flow inside one train step:
  1. local grads via ``jax.value_and_grad`` of the per-device loss;
  2. model-replicated leaves (norms, KV projections, router) are psum'd over
     the ``model`` axis (their true gradient sums each rank's path);
  3. ``GradSync`` synchronizes over ``data`` (+ ``pod``) — this step IS the
     paper's subject.  The pytree is partitioned into fixed-byte buckets
     (``repro.core.buckets``): dense leaves fuse into flat psum buckets,
     row-sparse tables stay whole and get a per-tensor scheme (Zen or a
     baseline; 'auto' decides leaf-by-leaf from the cost model).  Bucket
     sync ops are emitted double-buffered (``repro.train.schedule``) so
     XLA's latency-hiding scheduler can overlap bucket *i*'s collective
     with bucket *i+1*'s encode.  ``SyncConfig.bucket_bytes=None`` keeps
     the monolithic per-leaf path bit-exactly;
  4. ZeRO-1 update: each (pod, data) rank updates its rows of every
     leaf's leading axis and the new rows are all-gathered back.

Serve steps (prefill / decode) use the sequence-sharded KV cache layout
from ``repro.models`` (context-parallel decode over ``model``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import scopes
from repro.core.zen import GradSync, SyncConfig
from repro.models.common import ShardCtx
from repro.models.model import Model
from repro.optim.optimizers import INITS, UPDATES, OptConfig, ef_residual_init


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    opt: OptConfig = OptConfig()
    sync: SyncConfig = SyncConfig()
    zero1: bool = True


# ---------------------------------------------------------------------------
# spec utilities
# ---------------------------------------------------------------------------

def _has_model(spec: P) -> bool:
    return any(
        s == "model" or (isinstance(s, tuple) and "model" in s)
        for s in spec if s is not None
    )


def batch_pspecs(batch_shapes: dict, ctx: ShardCtx, n_batch_shards: int) -> dict:
    """Shard dim0 over (pod, data) when divisible, else replicate."""
    out = {}
    for k, v in batch_shapes.items():
        if v.shape and v.shape[0] % n_batch_shards == 0 and n_batch_shards > 1:
            axes = tuple(a for a in ctx.batch_axes)
            out[k] = P(axes if len(axes) > 1 else axes[0],
                       *([None] * (len(v.shape) - 1)))
        else:
            out[k] = P(*([None] * len(v.shape)))
    return out


def zero_axes(ctx: ShardCtx):
    """Mesh axes the ZeRO-1 state shards over: every data-parallel axis
    (plus pod).  Flat: ("data",); node-split: ("dp_inter", "dp_intra") —
    jax collectives take the tuple as one flattened axis, so the ZeRO
    math is topology-agnostic."""
    head = (ctx.pod_axis,) if ctx.pod_axis else ()
    return head + ctx.dp_axes


def _zero_world(ctx: ShardCtx) -> int:
    return ctx.dp * (ctx.pods if ctx.pod_axis else 1)


# ---------------------------------------------------------------------------
# ZeRO-1 optimizer state
# ---------------------------------------------------------------------------

def opt_chunk_size(d0: int, world: int) -> int:
    return -(-d0 // world)


def moment_shape(local_shape: tuple, world: int) -> tuple:
    """Global ZeRO-1 moment shape for a per-device shard ``local_shape``
    (a scalar counts as (1,)): dim 0 padded to a multiple of ``world``."""
    d0, *rest = tuple(local_shape) or (1,)
    return (world * opt_chunk_size(d0, world), *rest)


def _local_shape(shape: tuple, spec: P, sizes: dict) -> tuple:
    """A leaf's global shape -> its per-device shard under ``spec``."""
    out = list(shape)
    for i, ax in enumerate(spec):
        if ax is None:
            continue
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            out[i] //= sizes.get(a, 1)
    return tuple(out)


def _device_world(ctx: ShardCtx) -> int:
    """Total devices in the mesh — the EF residual is fully per-device
    (each (pod, data, model) rank keeps its own compressed-bucket
    memory), so its global dim0 is the whole device count."""
    return ctx.dp * ctx.tp * (ctx.pods if ctx.pod_axis else 1)


def residual_axes(ctx: ShardCtx) -> tuple:
    """Mesh axes, in mesh order, that shard the residual's dim0."""
    head = (ctx.pod_axis,) if ctx.pod_axis else ()
    return head + ctx.dp_axes + (ctx.tp_axis,)


def init_opt_state(tcfg: TrainerConfig, params, ctx: ShardCtx, param_specs,
                   gradsync=None):
    """Global optimizer state.  ZeRO-1: per-leaf f32 moments in the shape
    of the LOCAL (per-device) param shard, dim 0 chunked over the zero
    axes (``moment_shape``).  When ``gradsync`` compresses with error
    feedback, a ``residual`` entry carries one zero f32 vector per
    compressed bucket and device (DESIGN.md §8)."""
    world = _zero_world(ctx)
    init = INITS[tcfg.opt.kind]

    def leaf(p, spec):
        if not tcfg.zero1:
            return init(p)
        local = _local_shape(p.shape, spec, ctx.axis_sizes)
        return init(jnp.zeros(moment_shape(local, world), jnp.float32))

    state = jax.tree.map(leaf, params, param_specs)
    out = {"leaves": state, "step": jnp.zeros((), jnp.int32)}
    res = _residual_struct(gradsync, ctx)
    if res is not None:
        out["residual"] = ef_residual_init(res)
    return out


def opt_pspecs(tcfg: TrainerConfig, param_specs, ctx: ShardCtx,
               gradsync=None):
    zaxes = zero_axes(ctx)

    def leaf(spec: P):
        moment_spec = P(zaxes) if tcfg.zero1 else spec
        return {k: moment_spec for k in INITS[tcfg.opt.kind](
            jnp.zeros((1,), jnp.float32))}

    leaves = jax.tree.map(leaf, param_specs,
                          is_leaf=lambda x: isinstance(x, P))
    out = {"leaves": leaves, "step": P()}
    res = _residual_struct(gradsync, ctx)
    if res is not None:
        out["residual"] = {k: P(residual_axes(ctx)) for k in res}
    return out


def abstract_opt_state(tcfg: TrainerConfig, param_shapes, ctx: ShardCtx,
                       param_specs, gradsync=None):
    world = _zero_world(ctx)
    names = list(INITS[tcfg.opt.kind](jnp.zeros((1,), jnp.float32)))

    def leaf(p, spec):
        shape = (moment_shape(_local_shape(p.shape, spec, ctx.axis_sizes),
                              world) if tcfg.zero1 else p.shape)
        return {k: jax.ShapeDtypeStruct(shape, jnp.float32) for k in names}

    out = {"leaves": jax.tree.map(leaf, param_shapes, param_specs),
           "step": jax.ShapeDtypeStruct((), jnp.int32)}
    res = _residual_struct(gradsync, ctx)
    if res is not None:
        out["residual"] = res
    return out


def _residual_struct(gradsync, ctx: ShardCtx):
    """Global ShapeDtypeStructs of the EF residual state, or None when the
    sync config keeps no residual (no compression, or ``:noef``)."""
    if gradsync is None or not gradsync.has_compression:
        return None
    sizes = {k: v.shape[0] for k, v in gradsync.init_residual().items()}
    if not sizes:
        return None
    n_dev = _device_world(ctx)
    return {k: jax.ShapeDtypeStruct((n_dev * s,), jnp.float32)
            for k, s in sizes.items()}


# ---------------------------------------------------------------------------
# the per-device train step
# ---------------------------------------------------------------------------

def local_param_shapes(param_shapes, param_specs, ctx: ShardCtx):
    """Global ShapeDtypeStructs -> per-device (shard_map-local) shapes."""
    def leaf(sds, spec):
        return jax.ShapeDtypeStruct(
            _local_shape(sds.shape, spec, ctx.axis_sizes), sds.dtype)

    return jax.tree.map(leaf, param_shapes, param_specs,
                        is_leaf=lambda x: isinstance(x, P))


def make_gradsync(model: Model, tcfg: TrainerConfig, param_specs,
                  param_shapes=None, sparsity_profiles=None) -> GradSync:
    """Build the trainer's GradSync OFFLINE (hash layouts, bucket plan,
    compressor tags) from the local (per-device) grad shapes — grads
    match param shards inside shard_map.  The data-parallel Topology
    comes from the ctx's node grouping (``--node-size``) with the sync
    config's α-β override; node_size == 1 builds the degenerate flat
    topology (bit-identical to the pre-topology trainer)."""
    from repro.core.topology import build_topology

    ctx = model.ctx
    if param_shapes is None:
        param_shapes = model.abstract()[0]
    grad_shapes = local_param_shapes(param_shapes, param_specs, ctx)
    topo = build_topology(ctx.dp, ctx.node_size, axis=ctx.dp_axis,
                          alpha_beta=tcfg.sync.alpha_beta)
    return GradSync(
        tcfg.sync, list(model.sparse_paths), grad_shapes, ctx.dp,
        data_axis=ctx.dp_axis, pod_axis=ctx.pod_axis,
        profiles=sparsity_profiles, topology=topo)


def make_train_step(model: Model, tcfg: TrainerConfig, param_specs,
                    param_shapes=None, sparsity_profiles=None,
                    gradsync: GradSync | None = None):
    """Returns the per-device step fn (to be wrapped in shard_map).

    ``sparsity_profiles`` (optional ``{leaf-path: SparsityProfile}``) feeds
    measured densification/skew curves into GradSync's per-tensor 'auto'
    scheme choice (otherwise the worst-case budget profile decides).
    Callers that also build the optimizer state pass the ``gradsync`` they
    got from ``make_gradsync`` so the residual shape contract is shared."""
    ctx = model.ctx
    world = _zero_world(ctx)
    zaxes = zero_axes(ctx)

    spec_leaves = jax.tree.leaves(
        param_specs, is_leaf=lambda x: isinstance(x, P))

    if gradsync is None:
        gradsync = make_gradsync(model, tcfg, param_specs, param_shapes,
                                 sparsity_profiles)

    def loss_fn(params, batch):
        with jax.named_scope(scopes.FWD):
            return model.train_loss(params, batch)

    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)

        # --- 2. complete model-replicated grads over the model axis --------
        if ctx.tp > 1:
            flat_g, treedef = jax.tree.flatten(grads)
            flat_g = [
                g if _has_model(s) else lax.psum(g, ctx.tp_axis)
                for g, s in zip(flat_g, spec_leaves)
            ]
            grads = jax.tree.unflatten(treedef, flat_g)

        # --- 3. data(+pod)-axis sync: bucketed, overlap-scheduled -----------
        # (with EF compression the residual memory rides in opt_state and
        # is threaded through the sync — DESIGN.md §8)
        new_residual = None
        if gradsync.has_compression:
            grads, new_residual, sync_stats = gradsync(
                grads, opt_state.get("residual", {}),
                step=opt_state["step"])
        else:
            grads, sync_stats = gradsync(grads)
        metrics = {**metrics, **sync_stats}

        with jax.named_scope(scopes.OPT):
            # --- grad clip (global norm; sharded leaves psum over model) ----
            # The factor is applied in float32 inside each leaf's update (to
            # the rank's chunk under ZeRO-1): rounded to bf16 it would scale
            # every bf16 gradient by up to 0.2 % too much or little.
            scale = None
            if tcfg.opt.grad_clip > 0:
                flat_g, _ = jax.tree.flatten(grads)
                sq = jnp.float32(0)
                for g, s in zip(flat_g, spec_leaves):
                    ss = jnp.sum(g.astype(jnp.float32) ** 2)
                    if ctx.tp > 1 and _has_model(s):
                        ss = lax.psum(ss, ctx.tp_axis)
                    sq = sq + ss
                gn = jnp.sqrt(sq)
                scale = jnp.minimum(1.0, tcfg.opt.grad_clip / (gn + 1e-9))
                metrics["grad_norm"] = gn

            # --- 4. parameter update ----------------------------------------
            step = opt_state["step"]
            r = lax.axis_index(zaxes) if (world > 1) else 0

            def leaf_update(p, g, st):
                if tcfg.zero1:
                    return zero1_update(tcfg.opt, p, g, st, step, r, world,
                                        zaxes, scale)
                return UPDATES[tcfg.opt.kind](tcfg.opt, p, _scaled(g, scale),
                                              st, step)

            new_params, new_s = _zip_update(
                params, grads, opt_state["leaves"], leaf_update)
            new_state = {"leaves": new_s, "step": step + 1}

        if "residual" in opt_state:
            # EF memory: per-device state, untouched by ZeRO chunking
            new_state["residual"] = new_residual

        # report metrics averaged over data
        metrics = jax.tree.map(
            lambda m: lax.pmean(jnp.asarray(m, jnp.float32), zaxes)
            if world > 1 else jnp.asarray(m, jnp.float32), metrics)
        return new_params, new_state, metrics

    return step_fn


def _scaled(g, scale):
    """g times the clip factor, in float32 (g as it is without one)."""
    return g if scale is None else g.astype(jnp.float32) * scale


def zero1_update(opt: OptConfig, p, g, st, step, r, world: int, zaxes,
                 scale=None):
    """ZeRO-1 update of one leaf: rank ``r`` owns rows [r*c0, (r+1)*c0) of
    dim 0, ``st`` their moments; p and g keep their dtypes, and the rank's
    rows of g are multiplied by the clip factor ``scale`` (if any) in
    float32.  Zero rows pad dim 0 where ``world`` does not divide it;
    their moments stay zero."""
    shape = p.shape or (1,)
    c0 = opt_chunk_size(shape[0], world)
    pad = world * c0 - shape[0]

    def mine(x):
        x = x.reshape(shape)
        if pad:
            x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (len(shape) - 1))
        return lax.dynamic_slice_in_dim(x, r * c0, c0, 0)

    p_new, st_new = UPDATES[opt.kind](opt, mine(p), _scaled(mine(g), scale),
                                      st, step)
    if world > 1:
        with jax.named_scope(scopes.ZERO1_GATHER):
            p_new = lax.all_gather(p_new, zaxes, axis=0, tiled=True)
        # XLA:TPU copies a gathered result into the donated parameter; as
        # a bare `copy` it also copies the parameter on entry (1.2 GB a step,
        # qwen2-0.5b at dp=4 on a v5e), through this exact fusion it does not.
        p_new = lax.reduce_precision(
            p_new.astype(jnp.float32), exponent_bits=8,
            mantissa_bits=jnp.finfo(p.dtype).nmant).astype(p.dtype)
    if pad:
        p_new = p_new[:shape[0]]
    return p_new.reshape(p.shape), st_new


def _zip_update(params, grads, states, fn):
    """tree-map ``fn(p, g, st)`` where ``st`` is a sub-dict per param leaf."""
    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = tdef.flatten_up_to(grads)
    flat_s = tdef.flatten_up_to(states)
    outs = [fn(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
    new_p = jax.tree_util.tree_unflatten(tdef, [o[0] for o in outs])
    new_s = jax.tree_util.tree_unflatten(tdef, [o[1] for o in outs])
    return new_p, new_s


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(model: Model):
    def prefill_fn(params, batch):
        logits_l, cache = model.prefill(params, batch)
        return logits_l, cache
    return prefill_fn


def make_decode_step(model: Model, window: int = 0):
    def decode_fn(params, cache, tokens):
        nxt, logit_max, cache = model.decode(params, cache, tokens,
                                             window=window)
        return nxt, logit_max, cache
    return decode_fn


# ---------------------------------------------------------------------------
# cache partition specs (mirror of Model.make_cache structure)
# ---------------------------------------------------------------------------

def cache_pspecs(model: Model) -> Any:
    cfg, ctx = model.cfg, model.ctx
    b = ctx.batch_axes
    batch = b if len(b) > 1 else b[0]

    attn = {"k": P(batch, "model", None, None),
            "v": P(batch, "model", None, None),
            "pos": P("model")}
    mla = {"c": P(batch, "model", None), "kr": P(batch, "model", None),
           "pos": P("model")}
    ssm = {"state": P(batch, "model", None, None),
           "conv": P(batch, None, "model")}

    def lift(tree, n_lead=1):
        return jax.tree.map(lambda s: P(*([None] * n_lead), *s), tree,
                            is_leaf=lambda x: isinstance(x, P))

    out: dict = {"t": P()}
    if cfg.kind == "ssm":
        out["layers"] = lift(ssm)
    elif cfg.kind == "hybrid":
        out["ssm"] = lift(ssm, 2)
        if cfg.n_layers % cfg.shared_attn_every:
            out["ssm_tail"] = lift(ssm)
        out["attn"] = lift(attn)
    elif cfg.is_mla:
        for key, _, _ in model.stacks():
            out[key] = lift(mla)
    else:
        out["layers"] = lift(attn)
    if cfg.kind == "enc_dec":
        out["cross"] = P(None, None, batch, None, None, None)
    return out


def globalize_cache(local_tree, pspec_tree, mesh: Mesh):
    """Local-shard ShapeDtypeStructs -> global SDS given pspecs."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def leaf(sds, spec):
        shape = list(sds.shape)
        for i, ax in enumerate(spec):
            if ax is None:
                continue
            axs = ax if isinstance(ax, tuple) else (ax,)
            mult = int(np.prod([sizes[a] for a in axs]))
            shape[i] = shape[i] * mult
        return jax.ShapeDtypeStruct(tuple(shape), sds.dtype)

    return jax.tree.map(leaf, local_tree, pspec_tree)
