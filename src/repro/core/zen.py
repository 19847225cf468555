"""Gradient-synchronization API: Zen as a first-class trainer feature.

``GradSync`` maps a gradient pytree to its synchronized form inside a
``shard_map`` region.  Leaves named in ``sparse_rules`` (row-sparse tensors —
embedding tables in the assigned architectures) are synchronized with a
selectable sparse scheme over the data axis; everything else is a plain
``psum``.  A ``pod`` axis, when present, is reduced hierarchically after the
intra-pod sparse sync (paper §4.1 does the same with NVLink-intra /
network-inter).

Since the topology refactor (DESIGN.md §10) the data-parallel world itself
may be hierarchical: a two-level ``core/topology.py`` Topology (built from
``--node-size``) resolves every bucket to a **CommPlan** — e.g.
``hier(zen@dp_intra, agsparse@dp_inter)`` — whose stages run fastest level
first with capacities grown across the intra-merge boundary, and whose
stage 0 rides in its own fenced slot of the overlap schedule.  The flat
(degenerate) topology reproduces the single-axis stack bit-exactly.

Since the bucketed-scheduler refactor (DESIGN.md §7) the pytree is first
partitioned into fixed-byte buckets (``repro.core.buckets``): dense leaves
fuse into flat psum buckets, row-sparse leaves stay whole, and the per-bucket
sync ops are emitted double-buffered (``repro.train.schedule``) so XLA can
overlap bucket *i*'s collective with bucket *i+1*'s encode.
``bucket_bytes=None`` keeps the monolithic per-leaf path bit-exactly.

Scheme selection is a config knob so the paper's baselines are runnable
end-to-end (Fig. 11/12 reproduction), not just as microbenchmarks.  With
``scheme='auto'`` the choice is **per tensor**: each row-sparse leaf consults
its ``SparsityProfile`` (measured, via ``profiles``, or the worst-case budget
profile) through ``costmodel.choose_scheme``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.analysis import scopes
from repro.core import buckets as bk
from repro.core import costmodel, schemes, sparsify
from repro.core import topology as tpg
from repro.core.schemes import SyncStats, ZenLayout, make_zen_layout
from repro.core.topology import CommPlan, Topology, resolve_plan


@dataclasses.dataclass(frozen=True)
class SyncConfig:
    """How gradients are synchronized across the data-parallel axis."""

    scheme: str = "zen"           # any registry scheme (see registry.cli_scheme_choices()) | auto
    density_budget: float = 0.25  # capacity sizing for sparse buffers
    k: int = 3                    # Alg. 1 rehash rounds
    r1_factor: float = 2.0        # r1 = r1_factor * nnz_budget / n  (paper: 2)
    r2_ratio: float = 0.1         # r2 = r2_ratio * r1               (paper: 0.1)
    use_hash_bitmap: bool = True  # Alg. 2 on Pull (Fig. 18 ablation knob)
    seed: int = 0
    # 'auto' (beyond-paper): per-leaf offline choice — Zen wins iff the COO
    # push + bitmap pull volume under the density budget beats dense ring
    # allreduce; otherwise that leaf falls back to dense.  This prevents
    # Zen from LOSING on high-density tensors (paper Fig. 17's crossover).
    # The volume comparison lives in costmodel.choose_scheme, shared with
    # the Fig. 7 analytics.
    auto_threshold: float = 1.0   # zen_volume < threshold * dense_volume
    # Compute route for Zen's encode/decode stages: "xla" (pure jnp) or
    # "pallas" (fused kernels via repro.kernels.ops; interpret mode off-TPU).
    backend: str = "xla"
    # Pallas backend only: route the encode path through the single-dispatch
    # megakernel (kernels/zen_encode.py, DESIGN.md §11) instead of the
    # 3-dispatch hash/extract/pack chain.  Both are bit-exact vs XLA.
    fused_encode: bool = True
    # Pallas backend only: route the commit path (server aggregation +
    # compaction + bitmap pack, and the batched pull decode) through the
    # commit megakernel pair (kernels/zen_commit.py, DESIGN.md §14).
    # Wire-exact vs the unfused chain (zenlint's fused-commit route).
    fused_commit: bool = True
    # Path to a CostCalibrator JSON table (DESIGN.md §11).  When set, the
    # 'auto' scheme decision adds *measured* per-stage encode overhead —
    # zen is only picked when its wire win survives what encode actually
    # costs on this machine.  Produce with `python -m repro.core.costmodel
    # --calib-file PATH` (or let launch/train.py --calib-file calibrate on
    # first use).  None = analytic α-β model (the historical decision).
    calib_file: str | None = None
    # Bucketed overlap scheduling (DESIGN.md §7): fuse dense grads into
    # buckets of at most this many bytes and emit per-bucket sync ops
    # double-buffered.  None = monolithic per-leaf path (bit-exact PR-1).
    bucket_bytes: int | None = None
    # α-β link-parameter override for the topology cost model
    # (DESIGN.md §10): 'a_intra,b_intra,a_inter,b_inter' in (µs, µs/word),
    # or 'a,b' for every level.  None = the core/topology.py defaults.
    # Only consulted when the trainer builds a hierarchical topology
    # (--node-size > 1); the flat cost model is volume-only (degenerate).
    alpha_beta: str | None = None
    # Error-feedback sparsification of dense buckets (DESIGN.md §8): a
    # core/sparsify.py spec string — 'topk:0.01', 'randk:0.05',
    # 'threshold:1e-3', optional ':noef' suffix — or 'none'.  Compressed
    # buckets are synchronized with a sparse scheme (under 'auto' the
    # cost model decides per bucket from the post-compression density);
    # the EF residual lives in optimizer state and must be threaded
    # through ``GradSync.__call__(grads, residual, step=...)``.
    compress: str = "none"


class GradSync:
    """Synchronize a gradient pytree across ``data`` (and ``pod``) axes.

    Args:
      cfg: SyncConfig.
      sparse_paths: list of path substrings marking row-sparse leaves
          (e.g. ``["embed/table"]``).  Matched leaves must be 2-D
          ``[rows, d]`` row-sparse tensors.
      grad_shapes: pytree of ShapeDtypeStruct matching the grads — used to
          precompute Zen layouts and the bucket plan offline.
      n_data: size of the data axis.
      data_axis / pod_axis: mesh axis names ('pod' may be None).
      profiles: optional ``{leaf-path: SparsityProfile}`` of *measured*
          sparsity (e.g. from ``costmodel.profile_from_masks``).  Under
          scheme='auto' a profiled leaf is decided from its own curves
          instead of the worst-case density budget.
    """

    def __init__(
        self,
        cfg: SyncConfig,
        sparse_paths: list[str],
        grad_shapes: Any,
        n_data: int,
        data_axis: str = "data",
        pod_axis: str | None = None,
        profiles: dict[str, costmodel.SparsityProfile] | None = None,
        topology: Topology | None = None,
    ):
        self.cfg = cfg
        self.data_axis = data_axis
        self.pod_axis = pod_axis
        self.n_data = n_data
        # The flat degenerate topology reproduces the pre-topology stack
        # bit-exactly (α=0, β=1: time == volume, one level over data_axis)
        self.topology = (topology if topology is not None
                         else tpg.flat_topology(n_data, axis=data_axis))
        if self.topology.n != n_data:
            raise ValueError(
                f"topology covers {self.topology.n} workers "
                f"({self.topology.describe()}) but n_data={n_data}")
        if self.topology.flat and self.topology.intra.axis != data_axis:
            raise ValueError(
                f"flat topology axis {self.topology.intra.axis!r} != "
                f"data_axis {data_axis!r}")
        self.sparse_paths = tuple(sparse_paths)
        self.compress = sparsify.parse_compress(cfg.compress)
        self._layouts: dict[tuple[str, int], ZenLayout] = {}
        profiles = profiles or {}
        topo = self.topology
        # measured-time calibration (DESIGN.md §11): loaded once at plan
        # time; every 'auto' decision below then prices encode overhead
        self.calib = (costmodel.CalibrationTable.load(cfg.calib_file)
                      if cfg.calib_file else None)

        def auto_target():
            """What 'auto' hands to choose_scheme: the historical int
            world size on flat topologies (bit-identical picks), the
            α-β topology when hierarchical (plan tags)."""
            return max(n_data, 2) if topo.flat else topo

        def resolve_scheme(name: str, leaf) -> str:
            """Per-tensor plan tag for one row-sparse leaf (bucket
            planner callback).  'auto' consults the leaf's own profile."""
            if len(leaf.shape) > 2:
                raise ValueError(
                    f"sparse leaf {name} must be 2-D, got {leaf.shape}")
            if cfg.scheme != "auto":
                return cfg.scheme
            rows = leaf.shape[0] if len(leaf.shape) >= 1 else 1
            d = leaf.shape[1] if len(leaf.shape) > 1 else 1
            prof = profiles.get(name)
            if prof is None:
                prof = costmodel.worst_case_profile(
                    rows, cfg.density_budget, vw=max(d, 1))
            return costmodel.choose_scheme(
                prof, auto_target(), threshold=cfg.auto_threshold,
                calib=self.calib)

        def resolve_compressed(key: str, size: int) -> str:
            """Plan tag for one EF-compressed dense bucket: 'auto' runs
            the cost model on the measured profile when one is available
            (the DensityController feedback loop), else on the configured
            keep-density's worst case."""
            if cfg.scheme != "auto":
                return cfg.scheme
            prof = profiles.get(key)
            if prof is None:
                prof = sparsify.compress_profile(self.compress, size)
            return costmodel.choose_scheme(
                prof, auto_target(), threshold=cfg.auto_threshold,
                calib=self.calib)

        self.plan = bk.make_bucket_plan(
            grad_shapes, self._is_sparse, cfg.bucket_bytes, resolve_scheme,
            compress=self.compress.tag(),
            compressed_scheme=resolve_compressed)
        # per-bucket executable CommPlans + per-(bucket, level) layouts
        self._plans: dict[int, CommPlan] = {
            b.bid: resolve_plan(b.scheme, topo) for b in self.plan.buckets}
        for b in self.plan.buckets:
            cplan = self._plans[b.bid]
            if b.kind == bk.SPARSE:
                slot = b.slots[0]
                rows = slot.shape[0] if len(slot.shape) >= 1 else 1
                budget = cfg.density_budget
            elif b.compress != "none":
                # compressed dense bucket: flat element-sparse payload
                rows = b.size
                budget = self._compressed_budget()
            else:
                continue  # plain dense psum bucket: no sparse buffers
            for stage in cplan.stages:
                lvl = topo.levels[stage.level]
                if stage.scheme != "zen" or lvl.size <= 1:
                    continue
                self._layouts[b.key, stage.level] = make_zen_layout(
                    rows, lvl.size,
                    density_budget=self._level_budget(budget, stage.level),
                    key=cfg.seed,
                    k=cfg.k, r1_factor=cfg.r1_factor, r2_ratio=cfg.r2_ratio,
                )

    def _is_sparse(self, name: str) -> bool:
        return any(s in name for s in self.sparse_paths)

    def _level_budget(self, budget: float, level: int) -> float:
        """Capacity budget for a stage at ``level`` — delegates to
        ``schemes.level_budget`` (the one shared implementation of the
        DESIGN.md §10 capacity-growth boundary; the simulate_hier test
        harnesses and benchmarks use the same function)."""
        return schemes.level_budget(self.topology, budget, level)

    def _compressed_budget(self) -> float:
        """Capacity budget for compressed buckets: 4x the configured
        keep-density (EF bursts and threshold drift need headroom; the
        overflow counters surface genuine violations — DESIGN.md §2)."""
        return min(1.0, 4 * self.compress.density)

    # -- error-feedback residual state ---------------------------------------

    @property
    def has_compression(self) -> bool:
        return self.compress.enabled

    def compressed_buckets(self) -> dict[str, int]:
        """{bucket key: payload element count} for every compressed
        bucket — the shape contract for residual state and the
        DensityController."""
        return {b.key: b.size for b in self.plan.buckets
                if b.compress != "none"}

    def bucket_schemes(self) -> dict[str, str]:
        """{bucket key: resolved scheme} for compressed buckets (what the
        DensityController compares its recommendations against)."""
        return {b.key: b.scheme for b in self.plan.buckets
                if b.compress != "none"}

    def describe(self) -> list[str]:
        """One human-readable line per bucket: the resolved CommPlan
        (tag expanded over the topology), kind, size, and compressor —
        what ``launch/train.py --node-size``/``dryrun.py`` print so the
        plan a run executes is visible, not inferred."""
        lines = [f"topology: {self.topology.describe()}"]
        if self.calib is not None:
            lines.append(
                f"calibration: {len(self.calib.entries)} measured entries "
                f"({self.calib.meta.get('device', '?')}) — 'auto' prices "
                f"encode overhead")
        for b in self.plan.buckets:
            cplan = self._plans[b.bid]
            stages = " ; ".join(
                f"{s.scheme}@{self.topology.levels[s.level].axis}"
                f"[{self.topology.levels[s.level].size}]"
                for s in cplan.stages)
            comp = "" if b.compress == "none" else f" compress={b.compress}"
            lines.append(
                f"bucket {b.bid:3d} {b.kind:11s} {b.nbytes:>10d}B "
                f"plan=[{stages}]{comp}  {b.key}")
        return lines

    def init_residual(self) -> dict:
        """Zero EF residual memory (one f32 vector per compressed bucket;
        empty when EF is off — plain lossy compression keeps no state)."""
        if not (self.compress.enabled and self.compress.ef):
            return {}
        return {k: jnp.zeros((s,), jnp.float32)
                for k, s in self.compressed_buckets().items()}

    # -- per-bucket sync ------------------------------------------------------

    def _stage_args(self, bucket: bk.Bucket, scheme: str,
                    level: int) -> schemes.StageArgs:
        """Typed :class:`StageArgs` for one plan stage of one bucket:
        capacities grow with the merged density after earlier levels.
        Provisioning lives in ``schemes.stage_args_for`` — the single
        shared implementation the test harnesses and benchmarks also
        route through."""
        cfg = self.cfg
        capd = (self._compressed_budget() if bucket.compress != "none"
                else cfg.density_budget)
        rows = (bucket.slots[0].shape[0] if bucket.kind == bk.SPARSE
                else bucket.size)
        return schemes.stage_args_for(
            scheme, rows=rows, budget=self._level_budget(capd, level),
            layout=self._layouts.get((bucket.key, level)),
            use_hash_bitmap=cfg.use_hash_bitmap, backend=cfg.backend,
            fused=cfg.fused_encode, fused_commit=cfg.fused_commit)

    @jax.named_scope(scopes.SYNC_ENCODE)
    def _encode_bucket(self, bucket: bk.Bucket, payload: jnp.ndarray):
        """Local, collective-free stage (overlappable with the previous
        bucket's wire time).  Buckets whose FIRST plan stage is Zen
        encode to (indices, values); everything else passes through.
        For compressed buckets the payload arriving here is already
        EF-sparsified (the schedule's compress hook runs in the same
        pipeline slot)."""
        stage0 = self._plans[bucket.bid].stages[0]
        if (stage0.scheme == "zen"
                and self.topology.levels[0].size > 1):
            enc = schemes.zen_encode(
                payload, layout=self._layouts[bucket.key, 0],
                backend=self.cfg.backend, fused=self.cfg.fused_encode)
            return (payload, enc)
        return (payload,)

    def _run_stage(self, bucket: bk.Bucket, level: int, g, enc=None):
        """Execute one plan stage; ``enc`` carries the prefetched
        ZenEncoded for stage 0 (the overlap schedule's contract)."""
        cplan = self._plans[bucket.bid]
        stage = cplan.stages[level]
        lvl = self.topology.levels[level]
        if lvl.size <= 1:
            return g, SyncStats(sent_words=jnp.float32(0),
                                overflow=jnp.int32(0))
        if stage.scheme == "zen" and enc is not None:
            return schemes.zen_commit(
                enc, g, axis=lvl.axis,
                layout=self._layouts[bucket.key, level],
                use_hash_bitmap=self.cfg.use_hash_bitmap,
                backend=self.cfg.backend, fused=self.cfg.fused_commit)
        args = self._stage_args(bucket, stage.scheme, level)
        return schemes.stage_sync(stage.scheme, g, axis=lvl.axis,
                                  n=lvl.size, stage_args=args)

    @jax.named_scope(scopes.SYNC_EXCHANGE)
    def _intra_bucket(self, bucket: bk.Bucket, enc):
        """Hierarchical stage 0: aggregate over the fast (intra) axis.
        Only wired into the schedule on two-level topologies — the
        pipeline fences it against the next bucket's encode so the cheap
        hop hides under compute (train/schedule.py)."""
        g = enc[0]
        zen_enc = enc[1] if len(enc) > 1 else None
        g1, st = self._run_stage(bucket, 0, g, enc=zen_enc)
        return (g1, st)

    @jax.named_scope(scopes.SYNC_EXCHANGE)
    def _commit_bucket(
        self, bucket: bk.Bucket, enc
    ) -> tuple[jnp.ndarray, SyncStats]:
        """Collective + decode-apply stage for one bucket.  Dispatch is
        by the bucket's CommPlan: an uncompressed dense bucket is a fused
        psum (per level); a compressed dense bucket goes through the
        sparse schemes on its flat (element-sparse) payload exactly like
        a row-sparse leaf.  On flat topologies this is the whole sync; on
        two-level topologies ``_intra_bucket`` already ran stage 0 and
        ``enc`` is ``(intra-aggregated payload, stage-0 stats)``."""
        n = self.n_data
        if self.topology.flat:
            g = enc[0]
            zen_enc = enc[1] if len(enc) > 1 else None
            out, st = self._run_stage(bucket, 0, g, enc=zen_enc)
        else:
            g_mid, st0 = enc
            out, st1 = self._run_stage(bucket, 1, g_mid)
            st = SyncStats(
                sent_words=st0.sent_words + st1.sent_words,
                overflow=st0.overflow + st1.overflow,
                by_level=(st0.sent_words, st1.sent_words))
        if n > 1:
            out = out / n  # mean-reduce convention (all schemes SUM)
        if self.pod_axis is not None:
            out = lax.pmean(out, self.pod_axis)
        return out, st

    def encode_only(self, grads: Any) -> list:
        """Every bucket's local encode stage in isolation — no collectives,
        no mesh needed.  The measurement probe for the encode/commit time
        split (CostCalibrator, benchmarks/run.py ``stages``; DESIGN.md
        §11): wall-clock of this minus the full ``__call__`` attributes
        the e2e time stage-by-stage.  Uncompressed payloads only (the
        compress hook needs residual state — use ``__call__`` for that)."""
        from repro.train import schedule

        flat, _ = jax.tree_util.tree_flatten(grads)
        payloads = [bk.gather_bucket(b, flat) for b in self.plan.buckets]
        return schedule.encode_all(
            self.plan.buckets, payloads, self._encode_bucket)

    # -- pytree sync ----------------------------------------------------------

    def _compress_hook(self, residual, step, new_res: dict, extra: dict):
        """Build the schedule's compress stage.  Sparsified payloads flow
        on; residual updates and measured local densities d(1) are
        recorded in the caller's ``new_res`` / ``extra`` side channels."""
        ccfg = self.compress
        step = jnp.int32(0) if step is None else step

        def hook(bucket: bk.Bucket, payload):
            if bucket.compress == "none":
                return payload
            key = None
            if ccfg.kind == "randk":
                key = jax.random.fold_in(jax.random.fold_in(
                    jax.random.PRNGKey(ccfg.seed), bucket.bid), step)
            r = residual[bucket.key] if ccfg.ef else None
            with jax.named_scope(scopes.SYNC_ENCODE):
                sent, r_new, d1 = sparsify.compress_bucket(
                    ccfg, payload, r, key=key)
            if r_new is not None:
                new_res[bucket.key] = r_new
            extra[sparsify.DENSITY1_KEY.format(key=bucket.key)] = d1
            return sent

        return hook

    def __call__(self, grads: Any, residual: dict | None = None, *,
                 step: jnp.ndarray | None = None):
        """Synchronize grads (mean over data[, pod]).

        Without compression: ``gs(grads) -> (synced, stats)``.  With
        compression, the EF residual state must be threaded through:
        ``gs(grads, residual, step=t) -> (synced, new_residual, stats)``
        (``step`` feeds randk's deterministic mask stream; topk/threshold
        ignore it).  Passing ``residual`` always selects the 3-tuple form
        so callers keep one code path per configuration.
        """
        # deferred: core must not import the train layer at module scope
        from repro.train import schedule

        if self.compress.enabled and self.compress.ef and residual is None:
            raise ValueError(
                "EF compression keeps residual state: call "
                "gs(grads, residual) with gs.init_residual() (or the "
                "optimizer-state copy) — a fresh zero residual every step "
                "would silently disable error feedback")
        new_res: dict = {}
        extra: dict = {}
        compress_fn = (self._compress_hook(residual, step, new_res, extra)
                       if self.compress.enabled else None)
        flat, treedef = jax.tree_util.tree_flatten(grads)
        with jax.named_scope(scopes.SYNC_ENCODE):
            payloads = [bk.gather_bucket(b, flat) for b in self.plan.buckets]
        outs, per_bucket = schedule.run_schedule(
            self.plan.buckets, payloads,
            self._encode_bucket, self._commit_bucket, compress=compress_fn,
            intra=None if self.topology.flat else self._intra_bucket)
        synced_flat = list(flat)
        for b, out in zip(self.plan.buckets, outs):
            if b.compress != "none":
                # measured post-aggregation density d(n): the second point
                # of the DensityController's feedback profile
                extra[sparsify.DENSITYN_KEY.format(key=b.key)] = jnp.mean(
                    (out != 0).astype(jnp.float32))
            with jax.named_scope(scopes.SYNC_ENCODE):
                bk.scatter_bucket(b, out, synced_flat)
        synced = jax.tree_util.tree_unflatten(treedef, synced_flat)
        stats = bk.reduce_stats(self.plan, per_bucket, extra)
        if residual is None:
            return synced, stats
        return synced, new_res, stats
