"""Executable communication schemes for sparse tensor synchronization (§2.3).

Every scheme is an SPMD function of the *local* dense gradient, written
against ``jax.lax`` collectives with a named axis.  The same code runs:

* under ``jax.vmap(..., axis_name=AXIS)`` — single-device simulation used by
  unit/property tests and traffic accounting;
* under ``jax.shard_map`` over a real mesh axis — used by the trainer and the
  multi-pod dry-run.

Static-shape discipline (DESIGN.md §3): sparse buffers have fixed capacities.
A scheme's capacity requirement *is* its traffic claim — imbalanced schemes
(Sparse PS, OmniReduce) must provision ``skew × nnz/n`` per partition where
balanced ones provision ``nnz/n``; overflow counters surface under-provisioning
instead of silently corrupting gradients.

Schemes (Table 2, plus the Ok-Topk family):
  dense_sync        Ring + incremental + parallelism + balanced (psum).
  agsparse_sync     AllGather of COO (one-shot, centralization).
  sparcml_sync      SSAR recursive-doubling, incremental, centralization.
  sparse_ps_sync    P2P + one-shot + parallelism, even-range partition
                    (imbalanced).
  omnireduce_sync   As Sparse PS but with the tensor-block format.
  balanced_sync     Ok-Topk-style load-balanced split-and-exchange: a
                    histogram rebalance sizes the index ranges so the
                    per-worker receive volume is O(nnz_global/n + bins)
                    regardless of skew (arXiv 2201.07598).
  zen_sync          Balanced Parallelism via hierarchical hashing + hash
                    bitmap — the paper's contribution.

Dispatch is by the scheme registry (``repro.core.registry``): every
scheme registers its executable, volume/round formulas, and typed
``StageArgs`` exactly once (at the bottom of ``core/costmodel.py``);
``stage_sync`` and the planner both read that single record.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import formats
from repro.core import registry as sreg
from repro.core.registry import BALANCED_BINS, StageArgs
from repro.core.hashing import (
    EMPTY,
    compact_indices,
    compact_rows,
    extract_partitions,
    hash_mod,
    hierarchical_hash,
    make_seeds,
)


class SyncStats(NamedTuple):
    """Per-worker accounting: wire words sent and capacity overflows.

    ``by_level`` tags wire words by topology level for hierarchical plans
    (fastest level first — ``(intra_words, inter_words)`` for a two-level
    plan); flat schemes leave it empty, meaning "all words at level 0".
    """

    sent_words: jnp.ndarray  # f32 scalar
    overflow: jnp.ndarray    # i32 scalar (total dropped non-zeros)
    by_level: tuple = ()     # per-level f32 wire words (hier plans only)


def _nnz(idx: jnp.ndarray) -> jnp.ndarray:
    return jnp.sum((idx != EMPTY).astype(jnp.float32))


def _vwidth(dense: jnp.ndarray) -> int:
    """Words per value: 1 for element-sparse, d for row-sparse."""
    return 1 if dense.ndim == 1 else dense.shape[-1]


def _mask(dense: jnp.ndarray) -> jnp.ndarray:
    return dense != 0 if dense.ndim == 1 else jnp.any(dense != 0, axis=-1)


def _gather_rows(dense: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather dense[idx] with EMPTY -> 0; idx may have any leading shape."""
    safe = jnp.where(idx == EMPTY, 0, idx)
    vals = dense[safe]
    dead = (idx == EMPTY) if dense.ndim == 1 else (idx == EMPTY)[..., None]
    return jnp.where(dead, 0, vals)


def _scatter_add(
    out: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray, *, offset=0
) -> jnp.ndarray:
    tgt = jnp.where(idx == EMPTY, out.shape[0], idx - offset)
    return out.at[tgt].add(vals, mode="drop")


def _scatter_unique(
    out: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray
) -> jnp.ndarray:
    """Collision-free scatter for provably disjoint targets (Thm. 2).

    Zen's pull decode recovers ``perm[offsets[p] + local_pos]`` — servers
    own non-overlapping index ranges (``offsets`` partitions ``[0, M)``)
    and positions within a range are unique, so no two live updates share
    a target.  ``.at[].set`` then equals add-into-zeros value-for-value
    (0 + v == v; only the sign of a -0.0 value could differ, which the
    wire contract treats as equal) while telling XLA the scatter needs no
    combiner."""
    tgt = jnp.where(idx == EMPTY, out.shape[0], idx)
    return out.at[tgt].set(vals, mode="drop")


def _coo_reduce(
    out: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray,
    *, backend: str = "xla", interpret: bool | None = None,
) -> jnp.ndarray:
    """The one batched segment-reduce every scheme's server aggregation
    uses: out [M(, d)] += vals at row idx, EMPTY / out-of-range dropped.
    Thin shim over ``kernels.ops.batched_coo_reduce_op`` (which owns the
    flatten + backend dispatch); idx/vals may carry any leading shape."""
    from repro.kernels import ops as kops  # deferred: kernels import core

    return kops.batched_coo_reduce_op(out, idx, vals, backend=backend,
                                      interpret=interpret)


# ---------------------------------------------------------------------------
# Dense baseline
# ---------------------------------------------------------------------------

def dense_sync(dense: jnp.ndarray, *, axis: str) -> tuple[jnp.ndarray, SyncStats]:
    """Ring allreduce (Horovod's AllReduce in the paper's evaluation)."""
    n = lax.axis_size(axis)
    out = lax.psum(dense, axis)
    words = jnp.float32(2 * (n - 1) / n) * dense.size
    return out, SyncStats(sent_words=words, overflow=jnp.int32(0))


# ---------------------------------------------------------------------------
# AGsparse
# ---------------------------------------------------------------------------

def agsparse_sync(
    dense: jnp.ndarray, *, axis: str, capacity: int
) -> tuple[jnp.ndarray, SyncStats]:
    """AllGather of fixed-capacity COO; every GPU aggregates everything."""
    coo = formats.coo_encode(dense, capacity)
    all_idx = lax.all_gather(coo.indices, axis)   # [n, C]
    all_val = lax.all_gather(coo.values, axis)    # [n, C(, d)]
    out = _coo_reduce(jnp.zeros_like(dense), all_idx, all_val)
    n = lax.axis_size(axis)
    sent = (n - 1) * _nnz(coo.indices) * (1 + _vwidth(dense))
    return out, SyncStats(sent_words=sent, overflow=coo.overflow)


# ---------------------------------------------------------------------------
# SparCML (SSAR_Recursive_double)
# ---------------------------------------------------------------------------

def sparcml_sync(
    dense: jnp.ndarray, *, axis: str, n: int, capacity: int
) -> tuple[jnp.ndarray, SyncStats]:
    """Recursive doubling with incremental aggregation and COO exchange.

    Stage s pairs rank with rank XOR 2^s; the exchanged set doubles in the
    worst case each stage (densification makes it sub-double in practice), so
    stage capacity is ``min(capacity * 2^s, M)``.
    """
    if n <= 0 or n & (n - 1) != 0:
        raise ValueError(
            f"sparcml_sync: recursive doubling needs a power-of-two worker "
            f"count, got n={n}. Pad the data-parallel axis to the next power "
            f"of two, or pick scheme='zen', which accepts any n.")
    acc = dense
    sent = jnp.float32(0)
    overflow = jnp.int32(0)
    vw = _vwidth(dense)
    for s in range(int(math.log2(n))):
        cap_s = min(capacity * (2 ** s) * 2, dense.shape[0])
        coo = formats.coo_encode(acc, cap_s)
        perm = [(i, i ^ (1 << s)) for i in range(n)]
        got_idx = lax.ppermute(coo.indices, axis, perm)
        got_val = lax.ppermute(coo.values, axis, perm)
        acc = _scatter_add(acc, got_idx, got_val)
        sent = sent + _nnz(coo.indices) * (1 + vw)
        overflow = overflow + coo.overflow
    return acc, SyncStats(sent_words=sent, overflow=overflow)


# ---------------------------------------------------------------------------
# Sparse PS (even-range partitioning — the imbalanced strawman)
# ---------------------------------------------------------------------------

def sparse_ps_sync(
    dense: jnp.ndarray, *, axis: str, n: int, cap_push: int, cap_pull: int
) -> tuple[jnp.ndarray, SyncStats]:
    """P2P + one-shot + parallelism with *even contiguous* partitions.

    Each device doubles as worker and server ``rank``.  Because the partition
    is positional, C3 skew concentrates non-zeros in few partitions: correct
    provisioning needs ``cap_push ≈ skew × nnz / n`` — the imbalance cost.
    """
    M = dense.shape[0]
    if M % n != 0:
        raise ValueError(
            f"sparse_ps_sync: even-range partitioning needs the tensor length "
            f"to divide by the worker count, got M={M}, n={n} "
            f"(M % n = {M % n}). Pad the tensor to "
            f"{(M + n - 1) // n * n} rows or use scheme='zen', whose hash "
            f"partitioning has no divisibility requirement.")
    shard = M // n
    vw = _vwidth(dense)
    # --- Push: split into n contiguous ranges, COO-encode each --------------
    parts = dense.reshape(n, shard, *dense.shape[1:])
    coo = jax.vmap(lambda d: formats.coo_encode(d, cap_push))(parts)
    # indices are local to the range; a2a delivers partition r to rank r
    got_idx = lax.all_to_all(coo.indices, axis, split_axis=0, concat_axis=0)
    got_val = lax.all_to_all(coo.values, axis, split_axis=0, concat_axis=0)
    # --- Server aggregation --------------------------------------------------
    buf = _coo_reduce(jnp.zeros((shard, *dense.shape[1:]), dense.dtype),
                      got_idx, got_val)
    # --- Pull: COO of the aggregated shard, all_gather -----------------------
    pull = formats.coo_encode(buf, cap_pull)
    all_idx = lax.all_gather(pull.indices, axis)  # [n, cap_pull]
    all_val = lax.all_gather(pull.values, axis)
    rank_off = (jnp.arange(n, dtype=jnp.int32) * shard)[:, None]
    glob = jnp.where(all_idx == EMPTY, EMPTY, all_idx + rank_off)
    out = _coo_reduce(jnp.zeros_like(dense), glob, all_val)
    sent = (jnp.sum(jax.vmap(_nnz)(coo.indices)) - _nnz(coo.indices[lax.axis_index(axis)])
            + (n - 1) * _nnz(pull.indices)) * (1 + vw)
    overflow = jnp.sum(coo.overflow) + pull.overflow
    return out, SyncStats(sent_words=sent, overflow=overflow)


# ---------------------------------------------------------------------------
# OmniReduce (tensor-block format, even-range partitioning)
# ---------------------------------------------------------------------------

def omnireduce_sync(
    dense: jnp.ndarray, *, axis: str, n: int, block: int,
    cap_push: int, cap_pull: int,
) -> tuple[jnp.ndarray, SyncStats]:
    """As Sparse PS but transmitting non-zero *blocks* (no per-element index).
    """
    M = dense.shape[0]
    if M % n != 0 or (M // n) % block != 0:
        raise ValueError(
            f"omnireduce_sync: needs M divisible by n*block so every worker's "
            f"contiguous range is a whole number of blocks, got M={M}, n={n}, "
            f"block={block}. Pad the tensor to "
            f"{(M + n * block - 1) // (n * block) * (n * block)} rows, shrink "
            f"`block`, or use scheme='zen' (no divisibility requirement).")
    shard = M // n
    parts = dense.reshape(n, shard, *dense.shape[1:])
    blk = jax.vmap(lambda d: formats.blocks_encode(d, block, cap_push))(parts)
    got_ids = lax.all_to_all(blk.block_ids, axis, split_axis=0, concat_axis=0)
    got_val = lax.all_to_all(blk.values, axis, split_axis=0, concat_axis=0)
    nb = shard // block
    buf = jnp.zeros((nb, block, *dense.shape[1:]), dense.dtype)
    tgt = jnp.where(got_ids == EMPTY, nb, got_ids).reshape(-1)
    buf = buf.at[tgt].add(got_val.reshape(-1, *got_val.shape[2:]), mode="drop")
    buf = buf.reshape(shard, *dense.shape[1:])
    pull = formats.blocks_encode(buf, block, cap_pull)
    all_ids = lax.all_gather(pull.block_ids, axis)
    all_val = lax.all_gather(pull.values, axis)
    rank_off = (jnp.arange(n, dtype=jnp.int32) * nb)[:, None]
    glob = jnp.where(all_ids == EMPTY, EMPTY, all_ids + rank_off)
    out_b = jnp.zeros((M // block, block, *dense.shape[1:]), dense.dtype)
    tgt = jnp.where(glob == EMPTY, M // block, glob).reshape(-1)
    out_b = out_b.at[tgt].add(all_val.reshape(-1, *all_val.shape[2:]),
                              mode="drop")
    out = out_b.reshape(M, *dense.shape[1:])
    vw = _vwidth(dense)
    wpb = block * vw + 1  # words per block on the wire (values + id)
    sent = (jnp.sum(jax.vmap(lambda i: _nnz(i))(blk.block_ids))
            - _nnz(blk.block_ids[lax.axis_index(axis)])
            + (n - 1) * _nnz(pull.block_ids)) * wpb
    overflow = jnp.sum(blk.overflow) + pull.overflow
    return out, SyncStats(sent_words=sent, overflow=overflow)


# ---------------------------------------------------------------------------
# Balanced split-and-exchange (Ok-Topk family, arXiv 2201.07598)
# ---------------------------------------------------------------------------

def balanced_sync(
    dense: jnp.ndarray, *, axis: str, n: int, cap_push: int,
    cap_pull: int | None = None, bins: int | None = None,
) -> tuple[jnp.ndarray, SyncStats]:
    """Load-balanced split-and-exchange allreduce.

    Where ``sparse_ps_sync`` partitions the index space into *even*
    contiguous ranges (so skewed nonzeros concentrate on few servers and
    correct provisioning costs ``skew x nnz/n`` — O(n·nnz_max) at full
    skew), this scheme *rebalances* the range boundaries per step:

    1. Compact local nonzero indices (budget ``cap_push``).
    2. Build a ``min(M, bins)``-bin equal-width histogram of the global
       nonzero multiset — one f32 allreduce of the local histograms.
    3. Assign contiguous bin ranges to destinations by the exclusive
       cumulative count: ``dest(j) = floor(cum(j) * n / total)``.  Every
       destination's range then holds at most ``total/n + max_bin``
       multiset entries — the balanced receive bound O(nnz_global/n +
       bin granularity), independent of skew.
    4. Split local nonzeros by destination, ``all_to_all`` the COO
       (global indices — no per-range offset bookkeeping), scatter-add
       into a length-M buffer, compact the aggregated range
       (``cap_pull``, default ``cap_push``), ``all_gather`` the reduced
       shards.

    Unlike zen there is no precomputed layout: the partition is a pure
    function of this step's histogram, so MoE-style routing shifts are
    absorbed step by step at the price of the histogram allreduce
    (``2 (n-1)/n * bins`` words, charged to ``sent_words``).
    """
    M = dense.shape[0]
    if cap_pull is None:
        cap_pull = cap_push
    B = min(M, bins or BALANCED_BINS)
    bw = -(-M // B)  # bin width (ceil), last bin may be ragged
    vw = _vwidth(dense)
    my_rank = lax.axis_index(axis)

    # --- 1. local compaction -------------------------------------------------
    # total sendable budget is n * cap_push (cap_push slots per
    # destination); the split below redistributes, it cannot grow
    cap_local = n * cap_push
    idx, ov_c = compact_indices(_mask(dense), cap_local)
    live = idx != EMPTY
    bin_of = jnp.where(live, jnp.where(live, idx, 0) // bw, B)

    # --- 2. global multiset histogram (f32 allreduce: counts < 2^24 exact) ---
    local_hist = jnp.zeros((B,), jnp.float32).at[bin_of].add(1.0, mode="drop")
    hist = lax.psum(local_hist, axis)
    hist_words = jnp.float32(2 * (n - 1) / n) * B

    # --- 3. balanced contiguous bin -> destination assignment ----------------
    cum = jnp.cumsum(hist)
    total = jnp.maximum(cum[-1], 1.0)
    excl = cum - hist                     # exclusive prefix counts
    dest_of_bin = jnp.clip(
        (excl * n / total).astype(jnp.int32), 0, n - 1)
    dest = jnp.where(live, dest_of_bin[jnp.clip(bin_of, 0, B - 1)], n)

    # --- 4. per-destination split + exchange ---------------------------------
    member = dest[None, :] == jnp.arange(n, dtype=jnp.int32)[:, None]
    lpos, ov_s = compact_rows(member, cap_push)           # [n, cap_push]
    pidx = jnp.where(lpos == EMPTY, EMPTY,
                     idx[jnp.clip(lpos, 0, cap_local - 1)])
    pval = _gather_rows(dense, pidx)
    got_idx = lax.all_to_all(pidx, axis, split_axis=0, concat_axis=0)
    got_val = lax.all_to_all(pval, axis, split_axis=0, concat_axis=0)

    # --- server aggregation over the full index space (global indices) -------
    buf = _coo_reduce(jnp.zeros_like(dense), got_idx, got_val)

    # --- pull: compact the aggregated range, allgather the reduced shards ----
    pull_idx, ov_p = compact_indices(_mask(buf), cap_pull)
    pull_val = _gather_rows(buf, pull_idx)
    all_idx = lax.all_gather(pull_idx, axis)              # [n, cap_pull]
    all_val = lax.all_gather(pull_val, axis)
    out = _coo_reduce(jnp.zeros_like(dense), all_idx, all_val)

    nnz_per_dest = jnp.sum(pidx != EMPTY, axis=1).astype(jnp.float32)
    push_sent = (jnp.sum(nnz_per_dest) - nnz_per_dest[my_rank]) * (1 + vw)
    pull_sent = (n - 1) * _nnz(pull_idx) * (1 + vw)
    stats = SyncStats(
        sent_words=push_sent + pull_sent + hist_words,
        overflow=ov_c + jnp.sum(ov_s) + ov_p,
    )
    return out, stats


# ---------------------------------------------------------------------------
# Zen: Balanced Parallelism via hierarchical hashing + hash bitmap
# ---------------------------------------------------------------------------

class _DeviceTables(NamedTuple):
    """ZenLayout's lookup tables as device-resident arrays (uploaded once)."""

    seeds: jnp.ndarray       # uint32 [k+1]
    perm: jnp.ndarray        # int32 [M]
    local_pos: jnp.ndarray   # int32 [M]
    offsets: jnp.ndarray     # int32 [n+1]


@dataclasses.dataclass(frozen=True)
class ZenLayout:
    """Offline-precomputed, worker-shared state for one tensor shape.

    Built once per (tensor length, n, h0 seed) — the paper broadcasts the
    hash seeds at job start; everything here is a pure function of those.
    """

    n: int
    length: int
    seeds: np.ndarray          # uint32 [k+1]
    perm: np.ndarray           # int32 [M]   (I_0 .. I_{n-1} concatenated)
    offsets: np.ndarray        # int32 [n+1]
    local_pos: np.ndarray      # int32 [M]   global idx -> rank inside its I_p
    cap_server: int            # max_i |I_i| (static server buffer size)
    # Alg. 1 capacities
    cap_index: int             # C: worker-side nnz budget
    r1: int
    r2: int
    k: int

    @property
    def cap_bitmap_words(self) -> int:
        return (self.cap_server + 31) // 32

    def device_tables(self) -> _DeviceTables:
        """The numpy tables as device arrays, uploaded on first use and cached
        on the layout — repeated traces of ``zen_sync`` reuse the same buffers
        instead of re-staging ~2M ints of constants per trace."""
        tabs = self.__dict__.get("_device_tables")
        if tabs is None:
            # the first call may happen inside a jit trace: force eager
            # upload so concrete arrays (not tracers) are cached
            with jax.ensure_compile_time_eval():
                tabs = _DeviceTables(
                    seeds=jnp.asarray(self.seeds, dtype=jnp.uint32),
                    perm=jnp.asarray(self.perm, dtype=jnp.int32),
                    local_pos=jnp.asarray(self.local_pos, dtype=jnp.int32),
                    offsets=jnp.asarray(self.offsets, dtype=jnp.int32),
                )
            object.__setattr__(self, "_device_tables", tabs)
        return tabs

    def static_seeds(self) -> tuple:
        """Seeds as compile-time python ints (the pallas hash kernel bakes
        them in, mirroring the paper's broadcast-at-startup)."""
        return tuple(int(s) for s in np.asarray(self.seeds))


def make_zen_layout(
    length: int,
    n: int,
    *,
    density_budget: float,
    key: int = 0,
    k: int = 3,
    r1_factor: float = 2.0,
    r2_ratio: float = 0.1,
) -> ZenLayout:
    """Precompute the Zen layout (offline; numpy, not traced).

    ``density_budget`` is the max per-worker density the buffers are sized
    for (the paper sizes r1 = 2 |G| d_G).  Per-partition parallel memory is
    ``r1 = r1_factor * C / n`` and serial memory ``r2 = r2_ratio * r1``.
    """
    seeds = np.asarray(make_seeds(key, k + 1))
    idx = np.arange(length, dtype=np.int64)
    p = np.asarray(hash_mod(jnp.asarray(idx, jnp.int32), seeds[0], n))
    order = np.argsort(p, kind="stable").astype(np.int32)
    counts = np.bincount(p, minlength=n)
    offsets = np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)
    local = np.empty(length, dtype=np.int32)
    local[order] = np.arange(length, dtype=np.int32) - offsets[p[order]]
    cap_index = max(32, int(math.ceil(length * density_budget)))
    r1 = max(8, int(math.ceil(r1_factor * cap_index / n)))
    r2 = max(4, int(math.ceil(r2_ratio * r1)))
    return ZenLayout(
        n=n, length=length, seeds=seeds, perm=order,
        offsets=offsets, local_pos=local,
        cap_server=int(counts.max()), cap_index=cap_index,
        r1=r1, r2=r2, k=k,
    )


class ZenEncoded(NamedTuple):
    """Output of ``zen_encode`` — everything the push collective needs."""

    pidx: jnp.ndarray      # int32 [n, r1+r2] partitioned indices
    pval: jnp.ndarray      # [n, r1+r2(, d)] gathered values
    overflow: jnp.ndarray  # i32: worker compaction + serial-memory overflow


def _resolve_backend(backend: str, interpret: bool | None) -> bool:
    if backend not in ("xla", "pallas"):
        raise ValueError(f"backend must be 'xla' or 'pallas', got {backend!r}")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return interpret


def zen_encode(
    dense: jnp.ndarray, *, layout: ZenLayout, backend: str = "xla",
    interpret: bool | None = None, fused: bool | None = None,
) -> ZenEncoded:
    """Zen stage 1: local sparsify + hierarchical hash + partition extract.

    Collective-free — this is the compute the bucketed schedule overlaps
    with the previous bucket's wire time (repro.train.schedule).

    ``fused`` (pallas backend only; default on) routes hash + insertion
    rounds + extraction through the single-dispatch megakernel
    (``kernels/zen_encode.py``, DESIGN.md §11) instead of the 3-dispatch
    chain; both are bit-exact vs the XLA path (CI kernel-parity job).
    """
    lo = layout
    n = lo.n
    interpret = _resolve_backend(backend, interpret)
    tabs = lo.device_tables()
    idx, ov_c = compact_indices(_mask(dense), lo.cap_index)
    if backend == "pallas":
        if fused is None or fused:
            from repro.kernels import ops  # deferred: kernels import schemes' deps

            pidx, _occ, ovf = ops.zen_encode_fused_op(
                idx, lo.static_seeds(), n, lo.r1, lo.r2,
                interpret=interpret)
            pval = _gather_rows(dense, pidx)
            return ZenEncoded(pidx=pidx, pval=pval, overflow=ov_c + ovf)
        part = hierarchical_hash(
            idx, n=n, r1=lo.r1, r2=lo.r2, k=lo.k, backend="pallas",
            interpret=interpret, static_seeds=lo.static_seeds())
    else:
        part = hierarchical_hash(
            idx, n=n, r1=lo.r1, r2=lo.r2, k=lo.k, seeds=tabs.seeds)
    pidx = extract_partitions(part, backend=backend, interpret=interpret)
    pval = _gather_rows(dense, pidx)             # [n, r1+r2(, d)]
    return ZenEncoded(pidx=pidx, pval=pval, overflow=ov_c + part.overflow)


def zen_commit(
    enc: ZenEncoded, dense: jnp.ndarray, *, axis: str, layout: ZenLayout,
    use_hash_bitmap: bool = True, backend: str = "xla",
    interpret: bool | None = None, fused: bool | None = None,
) -> tuple[jnp.ndarray, SyncStats]:
    """Zen stages 2-4: push all_to_all, server aggregation, bitmap pull.

    ``dense`` supplies only the output shape/dtype (no data dependency —
    every transmitted value already lives in ``enc``).

    Push, aggregate, and pull all run over ``axis``: a named axis and its
    ``layout`` (sized ``layout.n == axis size``) are one unit.  In a
    hierarchical CommPlan each *stage* brings its own (axis, layout)
    pair, which is how a plan's pull ends up on a different axis than an
    earlier stage's push — there is no valid cross-axis pull *within*
    one zen instance (another axis names a different worker set, whose
    servers hold different partitions).

    ``fused`` (pallas backend only; default on) routes the server-side
    work through the commit megakernel pair (``kernels/zen_commit.py``,
    DESIGN.md §14): aggregation + mask/compact + value gather + bitmap
    pack in one push dispatch, and the batched pull decode (unpack +
    compact_rows) in one pull dispatch.  Wire words and every transmitted
    payload are bit-identical to the unfused chain (zenlint R2 sweeps the
    fused route)."""
    lo = layout
    n = lo.n
    vw = _vwidth(dense)
    interpret = _resolve_backend(backend, interpret)
    fuse = backend == "pallas" and (fused is None or fused)
    tabs = lo.device_tables()
    pidx, pval = enc.pidx, enc.pval

    # --- 2. Push (balanced all_to_all) ---------------------------------------
    got_idx = lax.all_to_all(pidx, axis, split_axis=0, concat_axis=0)
    got_val = lax.all_to_all(pval, axis, split_axis=0, concat_axis=0)

    # --- 3+4a. server aggregation + pull-payload build -----------------------
    flat_idx = got_idx.reshape(-1)
    lp = jnp.where(flat_idx == EMPTY, lo.cap_server,
                   tabs.local_pos[jnp.where(flat_idx == EMPTY, 0, flat_idx)])
    got_v = got_val.reshape(-1, *dense.shape[1:])
    cap_pull = lo.r1 + lo.r2  # aggregated nnz per server <= sum of pushes
    if fuse:
        from repro.kernels import ops as kops  # deferred: kernels import core

        lpos, vals, bm, ov_p = kops.zen_commit_push_fused_op(
            lp, got_v, cap_server=lo.cap_server, cap_pull=cap_pull,
            interpret=interpret)
    else:
        buf = _coo_reduce(
            jnp.zeros((lo.cap_server, *dense.shape[1:]), dense.dtype),
            lp, got_v, backend=backend, interpret=interpret)
        srv_mask = _mask(buf)
        lpos, ov_p = compact_indices(srv_mask, cap_pull)
        vals = _gather_rows(buf, lpos)

    # --- 4b. Pull -------------------------------------------------------------
    if use_hash_bitmap:
        if not fuse:
            bm = formats.bitmap_encode(srv_mask, backend=backend,
                                       interpret=interpret)
        all_bm = lax.all_gather(bm, axis)                 # [n, W]
        all_val = lax.all_gather(vals, axis)              # [n, cap_pull(,d)]
        # fused decode: one batched unpack + compaction + permutation gather
        # (replaces the per-server vmapped closure)
        if fuse:
            lpos_all = formats.bitmap_decode_compact(
                all_bm, lo.cap_server, cap_pull, backend="pallas",
                interpret=interpret)
        else:
            m_all = formats.bitmap_decode_batch(
                all_bm, lo.cap_server, backend=backend, interpret=interpret)
            lpos_all, _ = compact_rows(m_all, cap_pull)   # [n, cap_pull]
        gidx = jnp.clip(tabs.offsets[:n, None] + lpos_all, 0, lo.length - 1)
        glob = jnp.where(lpos_all == EMPTY, EMPTY, tabs.perm[gidx])
        pull_words = (n - 1) * (_nnz(lpos) * vw + lo.cap_bitmap_words)
    else:  # COO pull (ablation)
        glob_l = jnp.where(
            lpos == EMPTY, EMPTY,
            tabs.perm[jnp.clip(tabs.offsets[lax.axis_index(axis)] + lpos,
                               0, lo.length - 1)])
        glob = lax.all_gather(glob_l, axis)
        all_val = lax.all_gather(vals, axis)
        pull_words = (n - 1) * _nnz(lpos) * (vw + 1)

    # final decode-apply stays in XLA on both backends: its output is the
    # full-length gradient, too large for the VMEM-resident scatter kernel
    # (which is sized for the compact server buffer).  Thm. 2 makes the
    # decoded targets globally unique, so it needs no combiner.
    out = _scatter_unique(jnp.zeros_like(dense), glob.reshape(-1),
                          all_val.reshape(-1, *dense.shape[1:]))

    my_rank = lax.axis_index(axis)
    push_sent = (jnp.sum(jax.vmap(_nnz)(pidx)) - _nnz(pidx[my_rank])) * (1 + vw)
    stats = SyncStats(
        sent_words=push_sent + pull_words,
        overflow=enc.overflow + ov_p,
    )
    return out, stats


def zen_sync(
    dense: jnp.ndarray, *, axis: str, layout: ZenLayout,
    use_hash_bitmap: bool = True, backend: str = "xla",
    interpret: bool | None = None, fused: bool | None = None,
    fused_commit: bool | None = None,
) -> tuple[jnp.ndarray, SyncStats]:
    """Zen synchronization: Alg. 1 push + Alg. 2 (hash bitmap) pull.

    1. Compact local non-zero indices; hierarchically hash into n balanced
       partitions (h0 fixes the server; h1..hk + serial memory place them).
    2. Push: all_to_all of (indices, values) — balanced by Thm. 2.
    3. Aggregate: each server scatter-adds into its compact partition buffer
       (positions = offline local_pos, so same index from all workers lands
       in the same slot — complete aggregation).
    4. Pull: all_gather of (hash bitmap, non-zero values) — constant-size
       index metadata by Thm. 3.  With ``use_hash_bitmap=False``, pull uses
       COO (the Fig. 18 ablation).

    ``backend`` selects the compute route for the encode/decode stages:
    "xla" is pure jnp; "pallas" fuses the hash stage, bitmap pack/unpack,
    row compaction, and scatter-add through ``repro.kernels.ops`` (interpret
    mode off-TPU, real kernels on TPU).  Both routes are sort-free and
    value-identical.

    Implemented as ``zen_encode`` (stage 1, collective-free) followed by
    ``zen_commit`` (stages 2-4) — the split the bucketed overlap schedule
    pipelines (DESIGN.md §7).
    """
    enc = zen_encode(dense, layout=layout, backend=backend,
                     interpret=interpret, fused=fused)
    return zen_commit(enc, dense, axis=axis, layout=layout,
                      use_hash_bitmap=use_hash_bitmap, backend=backend,
                      interpret=interpret, fused=fused_commit)


# ---------------------------------------------------------------------------
# CommPlan execution: per-stage dispatch + the hierarchical composer
# ---------------------------------------------------------------------------

def stage_sync(
    scheme: str, dense: jnp.ndarray, *, axis: str, n: int,
    stage_args: StageArgs | None = None, **kw,
) -> tuple[jnp.ndarray, SyncStats]:
    """Run one scheme over one named axis — the uniform entry the
    CommPlan interpreter (``hier_sync``) and the bucket committer
    (``core/zen.py``) dispatch through.

    Dispatch is registry-driven (``repro.core.registry``): the scheme's
    :class:`SchemeSpec` names the executable function, the
    :class:`StageArgs` fields it consumes, and which are mandatory.
    Callers pass either a typed ``stage_args`` or loose keyword
    arguments (collected into one); validation raises config-named
    ValueErrors *before* the trace, so a mis-provisioned plan fails at
    plan-build time, not inside jit.  Capacity knobs are the caller's:
    a stage after an intra merge must provision for the *merged* density
    (``costmodel.merged_profile``), not the per-worker one — see
    :func:`plan_stage_args` for the one place that computes them."""
    spec = sreg.get_scheme(scheme)
    if stage_args is None:
        try:
            stage_args = StageArgs(**kw)
        except TypeError:
            valid = ", ".join(f.name for f in dataclasses.fields(StageArgs))
            bad = ", ".join(sorted(set(kw) - {
                f.name for f in dataclasses.fields(StageArgs)}))
            raise ValueError(
                f"stage_sync({scheme!r}): unknown stage arg(s) {bad}; "
                f"StageArgs fields are: {valid}") from None
    elif kw:
        raise ValueError(
            "stage_sync: pass a typed stage_args OR loose keyword "
            f"arguments, not both (got stage_args and {sorted(kw)})")
    sreg.validate_stage_args(spec, stage_args,
                             where=f"stage over axis {axis!r}")
    kwargs = sreg.stage_kwargs(spec, stage_args)
    if spec.needs_n:
        kwargs["n"] = n
    return spec.resolve_sync()(dense, axis=axis, **kwargs)


def level_budget(topology, budget: float, level: int) -> float:
    """Capacity budget for a plan stage at ``level``: stages after the
    intra merge provision for the worst-case merged density (the
    product of earlier level sizes' non-overlapping nonzeros in one
    tensor) — the capacity-growth boundary semantics of DESIGN.md §10.
    Level 0 passes the configured budget through untouched (the flat
    path must stay byte-identical to the pre-topology stack)."""
    if level == 0:
        return budget
    grow = math.prod(lv.size for lv in topology.levels[:level])
    return min(1.0, budget * grow)


def stage_args_for(
    scheme: str, *, rows: int, budget: float,
    layout: ZenLayout | None = None, use_hash_bitmap: bool = True,
    backend: str = "xla", interpret: bool | None = None,
    fused: bool | None = None, fused_commit: bool | None = None,
) -> StageArgs:
    """Provision one stage's :class:`StageArgs` from a density budget —
    the single place capacity sizing lives (GradSync, ``simulate_hier``
    harnesses, and benchmarks all route through here instead of
    hand-picking per-scheme kwargs).  ``cap = max(64, rows * budget)``
    with the omnireduce block split preserved bit-for-bit from the
    pre-registry provisioning."""
    cap = max(64, int(rows * budget))
    if scheme == "dense":
        return StageArgs()
    if scheme == "zen":
        return StageArgs(layout=layout, use_hash_bitmap=use_hash_bitmap,
                         backend=backend, interpret=interpret, fused=fused,
                         fused_commit=fused_commit)
    if scheme == "omnireduce":
        blk = 8
        nb = max(8, cap // blk)
        return StageArgs(block=blk, cap_push=nb, cap_pull=nb)
    # COO-capacity family: agsparse, sparcml, sparse_ps, balanced — the
    # registry's arg aliases fan ``capacity`` into cap_push/cap_pull.
    return StageArgs(capacity=cap)


def plan_stage_args(
    plan, topology, rows: int, *, density_budget: float, key: int = 0,
    k: int = 3, r1_factor: float = 2.0, r2_ratio: float = 0.1,
    backend: str = "xla", use_hash_bitmap: bool = True,
    fused: bool | None = None, fused_commit: bool | None = None,
    interpret: bool | None = None,
) -> dict[int, StageArgs]:
    """Provision every stage of a CommPlan: {level -> StageArgs}, with
    size-1 levels skipped (free identity — ``hier_sync`` never
    dispatches them) and capacity grown across the intra-merge boundary
    via :func:`level_budget`.  Zen stages get a fresh layout sized for
    the level's *merged* budget.  Each stage is validated against the
    registry so a bad plan fails here, with the level named, not inside
    the jit trace."""
    out: dict[int, StageArgs] = {}
    for stage in plan.stages:
        lvl = topology.levels[stage.level]
        if lvl.size <= 1:
            continue
        b = level_budget(topology, density_budget, stage.level)
        layout = None
        if stage.scheme == "zen":
            layout = make_zen_layout(rows, lvl.size, density_budget=b,
                                     key=key, k=k, r1_factor=r1_factor,
                                     r2_ratio=r2_ratio)
        args = stage_args_for(
            stage.scheme, rows=rows, budget=b, layout=layout,
            use_hash_bitmap=use_hash_bitmap, backend=backend,
            interpret=interpret, fused=fused, fused_commit=fused_commit)
        sreg.validate_stage_args(
            sreg.get_scheme(stage.scheme), args,
            where=f"plan stage {stage.scheme}@level{stage.level}")
        out[stage.level] = args
    return out


def hier_sync(
    dense: jnp.ndarray, *, topology, plan, stage_kw: dict | None = None,
) -> tuple[jnp.ndarray, SyncStats]:
    """Execute a CommPlan over a Topology: stage 0 aggregates over the
    fast (intra) axis, stage 1 runs on the *intra-aggregated* gradient
    over the slow (inter) axis.  Exact by associativity of the sum.

    ``stage_kw`` maps level index -> that stage's arguments, as either a
    typed :class:`StageArgs` (what :func:`plan_stage_args` builds) or a
    loose kwargs dict.  Size-1 levels are skipped (free identity) and
    report zero wire words.  Returns the
    SUM over all ``topology.n`` workers (same convention as every flat
    ``*_sync``) with ``SyncStats.by_level`` carrying the per-level wire
    split the inter-volume regression gate tracks."""
    stage_kw = stage_kw or {}
    g = dense
    sent = jnp.float32(0)
    overflow = jnp.int32(0)
    by_level = []
    for stage in plan.stages:
        lvl = topology.levels[stage.level]
        if lvl.size <= 1:
            by_level.append(jnp.float32(0))
            continue
        kw = stage_kw.get(stage.level, {})
        if isinstance(kw, StageArgs):
            g, st = stage_sync(stage.scheme, g, axis=lvl.axis,
                               n=lvl.size, stage_args=kw)
        else:
            g, st = stage_sync(stage.scheme, g, axis=lvl.axis,
                               n=lvl.size, **kw)
        sent = sent + st.sent_words
        overflow = overflow + st.overflow
        by_level.append(st.sent_words)
    return g, SyncStats(sent_words=sent, overflow=overflow,
                        by_level=tuple(by_level))


# ---------------------------------------------------------------------------
# Registry + single-device simulation helper
# ---------------------------------------------------------------------------

AXIS = "sync"


def simulate(fn, per_worker_dense: jnp.ndarray, **kwargs):
    """Run a scheme over [n, M(, d)] worker gradients on one device via vmap.

    Returns (aggregated [n, M(, d)] — identical rows, SyncStats batched)."""
    f = functools.partial(fn, axis=AXIS, **kwargs)
    return jax.vmap(f, axis_name=AXIS)(per_worker_dense)


def simulate_hier(per_worker_dense: jnp.ndarray, *, topology, plan,
                  stage_kw: dict | None = None, fn=None):
    """Single-device simulation of a hierarchical plan: [n, M(, d)] worker
    gradients nested-vmapped as [n_inter, n_intra, M(, d)] with one named
    axis per topology level (workers of a node are CONSECUTIVE rows —
    the same contiguous grouping ``launch/mesh.py`` builds).

    ``fn`` overrides the per-worker function (default: ``hier_sync`` of
    ``plan``); it receives the local dense gradient only."""
    topo = topology
    if fn is None:
        fn = functools.partial(hier_sync, topology=topo, plan=plan,
                               stage_kw=stage_kw)
    n_intra, n_inter = topo.intra.size, topo.inter.size
    per = per_worker_dense.reshape(
        n_inter, n_intra, *per_worker_dense.shape[1:])
    g = jax.vmap(jax.vmap(fn, axis_name=topo.intra.axis),
                 axis_name=topo.inter.axis)(per)
    return jax.tree.map(
        lambda x: x.reshape(n_inter * n_intra, *x.shape[2:]), g)
