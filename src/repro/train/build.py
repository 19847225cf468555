"""Glue: build jitted, mesh-mapped train / serve programs for an arch.

This is the layer the launcher, dry-run, smoke tests, and examples all call.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.analysis import scopes
from repro.data.pipeline import make_batch_specs
from repro.models.common import ArchConfig, make_ctx
from repro.models.model import (Model, assert_mesh_invariant_params,
                                build_model)
from repro.train import steps as st
from repro.train.steps import TrainerConfig


@dataclasses.dataclass
class Program:
    """A compiled-able distributed program bundle for one architecture."""

    cfg: ArchConfig
    model: Model
    mesh: Mesh
    tcfg: TrainerConfig
    param_shapes: Any
    param_specs: Any
    # jitted entry points (built lazily per mode)
    train_step: Any = None
    prefill_step: Any = None
    decode_step: Any = None
    batch_specs: Any = None
    cache_specs: Any = None
    # the trainer's GradSync (set by attach_train): owns the bucket plan,
    # compressor tags, and the EF-residual shape contract that the
    # optimizer state must match (DESIGN.md §8)
    gradsync: Any = None
    # measured sparsity profiles used at the last (re)plan — the
    # DensityController feedback loop writes here via attach_train
    sparsity_profiles: Any = None
    # (train_step, its compiled text): both step maps read one compile
    _step_text: Any = dataclasses.field(default=None, repr=False)

    def init_params(self, seed: int = 0):
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self.param_specs,
            is_leaf=lambda x: isinstance(x, P))
        fn = jax.jit(lambda k: self.model.init(k)[0],
                     out_shardings=shardings)
        return fn(jax.random.PRNGKey(seed))

    def fresh_cache(self):
        """A correctly-initialized global decode cache (zeros, pos = -1,
        t = 0).  Requires attach_serve(..., mode='decode') first."""
        shapes = self.cache_specs["global_shapes"]

        def leaf(path, s):
            name = str(getattr(path[-1], "key", ""))
            if name == "pos":
                return jnp.full(s.shape, -1, s.dtype)
            return jnp.zeros(s.shape, s.dtype)

        return jax.tree_util.tree_map_with_path(leaf, shapes)

    def init_opt(self, params):
        if self.gradsync is None and self.tcfg.sync.compress != "none":
            raise ValueError(
                "EF compression sizes the residual from the bucket plan: "
                "call attach_train(prog, ...) before init_opt")
        ospecs = st.opt_pspecs(self.tcfg, self.param_specs, self.model.ctx,
                               gradsync=self.gradsync)
        shardings = jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), ospecs,
            is_leaf=lambda x: isinstance(x, P))
        fn = jax.jit(functools.partial(st.init_opt_state, self.tcfg,
                                       ctx=self.model.ctx,
                                       param_specs=self.param_specs,
                                       gradsync=self.gradsync),
                     out_shardings=shardings)
        return fn(params)

    def step_scopes(self) -> dict:
        """``{HLO instruction name: layer}`` of the compiled train step
        (``analysis/scopes.py``): the step is lowered with the program's
        own abstract parameters, optimizer state and batch, in the
        shardings and with the donation of the real call, so the
        executable is the one the trainer runs (from the persistent
        cache where it is on).  Maps a device trace of the trainer, whose
        events are named by instruction, to fwd/bwd/remat/opt/sync."""
        return scopes.instruction_scopes(self._compiled_step_text())

    def step_blocks(self) -> dict:
        """``{HLO instruction name: block}`` of the same executable:
        ``moe.route``, ``moe.experts`` or ``other``, in whichever layer
        the instruction runs (``analysis/scopes.instruction_blocks``)."""
        return scopes.instruction_blocks(self._compiled_step_text())

    def _compiled_step_text(self) -> str:
        if self.train_step is None:
            raise ValueError("call attach_train(prog, ...) first")
        if self._step_text is None or self._step_text[0] is not self.train_step:
            self._step_text = (self.train_step, self._compile_step_text())
        return self._step_text[1]

    def _compile_step_text(self) -> str:
        ctx = self.model.ctx

        def placed(shapes, specs):
            return jax.tree.map(
                lambda s, p: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(self.mesh, p)),
                shapes, specs)

        params = placed(self.param_shapes, self.param_specs)
        opt = placed(
            st.abstract_opt_state(self.tcfg, self.param_shapes, ctx,
                                  self.param_specs, gradsync=self.gradsync),
            st.opt_pspecs(self.tcfg, self.param_specs, ctx,
                          gradsync=self.gradsync))
        batch = placed(self.batch_specs["shapes"], self.batch_specs["pspecs"])
        return self.train_step.lower(params, opt, batch).compile().as_text()


def build_program(cfg: ArchConfig, mesh: Mesh,
                  tcfg: TrainerConfig | None = None,
                  pad_heads: bool = False,
                  moe_a2a: bool = False) -> Program:
    from repro.core.topology import DP_INTER, DP_INTRA

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get("model", 1)
    pods = sizes.get("pod", 1)
    # a node-split mesh (launch/mesh.py --node-size) carries the data
    # parallelism as nested (dp_inter, dp_intra) axes; the ctx keeps dp as
    # the TOTAL data degree and records the node grouping separately
    node_size = sizes.get(DP_INTRA, 1)
    dp = sizes.get("data", 1) * sizes.get(DP_INTER, 1) * node_size
    ctx = make_ctx(cfg, tp, dp, pods, pad_heads=pad_heads, moe_a2a=moe_a2a,
                   node_size=node_size)
    model = build_model(cfg, ctx)
    shapes, specs = model.abstract()
    # hard contract (DESIGN.md §9): the global param pytree must not depend
    # on the mesh — cheap (abstract-only) and runs on every build
    assert_mesh_invariant_params(cfg, ctx, shapes)
    return Program(cfg=cfg, model=model, mesh=mesh,
                   tcfg=tcfg or TrainerConfig(),
                   param_shapes=shapes, param_specs=specs)


def attach_train(prog: Program, seq_len: int, global_batch: int,
                 sparsity_profiles=None) -> None:
    """Build prog.train_step: (params, opt_state, batch) -> (params, opt,
    metrics).

    ``sparsity_profiles`` ({bucket-key/leaf-path: SparsityProfile}) feeds
    measured density curves into the per-bucket 'auto' scheme choice —
    the DensityController replan path re-calls attach_train with the
    profiles it has learned (bucket boundaries and residual shapes are
    profile-independent, so existing params/opt_state stay valid)."""
    model, mesh, tcfg = prog.model, prog.mesh, prog.tcfg
    ctx = model.ctx
    n_shards = ctx.dp * (ctx.pods if ctx.pod_axis else 1)
    bshapes = make_batch_specs(prog.cfg, seq_len, global_batch, "train")
    bspecs = st.batch_pspecs(bshapes, ctx, n_shards)
    prog.sparsity_profiles = sparsity_profiles
    prog.gradsync = st.make_gradsync(model, tcfg, prog.param_specs,
                                     prog.param_shapes, sparsity_profiles)
    ospecs = st.opt_pspecs(tcfg, prog.param_specs, ctx,
                           gradsync=prog.gradsync)
    step_fn = st.make_train_step(model, tcfg, prog.param_specs,
                                 gradsync=prog.gradsync)
    metric_specs = P()
    mapped = jax.shard_map(
        step_fn, mesh=mesh,
        in_specs=(prog.param_specs, ospecs, bspecs),
        out_specs=(prog.param_specs, ospecs, metric_specs),
        check_vma=False)
    prog.train_step = jax.jit(mapped, donate_argnums=(0, 1))
    prog.batch_specs = {"shapes": bshapes, "pspecs": bspecs}


def attach_serve(prog: Program, seq_len: int, global_batch: int,
                 mode: str) -> None:
    """Build prog.prefill_step / prog.decode_step for an input shape."""
    model, mesh = prog.model, prog.mesh
    cfg, ctx = prog.cfg, model.ctx
    n_shards = ctx.dp * (ctx.pods if ctx.pod_axis else 1)
    window = cfg.sliding_window if seq_len > 65536 else 0
    cache_len = min(seq_len, window) if window else seq_len

    if mode == "prefill":
        bshapes = make_batch_specs(cfg, seq_len, global_batch, "prefill")
        bspecs = st.batch_pspecs(bshapes, ctx, n_shards)
        cspecs = st.cache_pspecs(model)
        fn = st.make_prefill_step(model)
        mapped = jax.shard_map(
            fn, mesh=mesh, in_specs=(prog.param_specs, bspecs),
            out_specs=(P(bspecs["tokens"][0], "model"), cspecs),
            check_vma=False)
        prog.prefill_step = jax.jit(mapped)
        prog.batch_specs = {"shapes": bshapes, "pspecs": bspecs}
        prog.cache_specs = cspecs
        return

    # decode
    bshapes = make_batch_specs(cfg, seq_len, global_batch, "decode")
    bspecs = st.batch_pspecs(bshapes, ctx, n_shards)
    cspecs = st.cache_pspecs(model)
    batch_local = (global_batch // n_shards
                   if global_batch % n_shards == 0 and n_shards > 1
                   else global_batch)
    local_cache = model.make_cache(batch_local, cache_len, abstract=True)
    local_cache["t"] = jax.ShapeDtypeStruct((), jnp.int32)
    global_cache = st.globalize_cache(local_cache, cspecs, mesh)
    fn = st.make_decode_step(model, window=window)
    tok_spec = bspecs["tokens"]
    mapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(prog.param_specs, cspecs, tok_spec),
        out_specs=(tok_spec, P(tok_spec[0]), cspecs),
        check_vma=False)
    prog.decode_step = jax.jit(mapped, donate_argnums=(1,))
    prog.batch_specs = {"shapes": bshapes, "pspecs": bspecs}
    prog.cache_specs = {"pspecs": cspecs, "global_shapes": global_cache,
                        "local_shapes": local_cache, "window": window,
                        "cache_len": cache_len}
