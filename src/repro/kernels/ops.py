"""Jitted public wrappers around the Pallas kernels.

These handle padding / reshaping to the kernels' tile layouts and expose the
same signatures as the pure-jnp references in ``ref.py``.  ``interpret=True``
(the default on CPU) executes the kernel bodies in Python for validation;
on TPU pass ``interpret=False``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hashing import EMPTY
from repro.kernels import bitmap as _bm
from repro.kernels import compact as _cp
from repro.kernels import hash_stage as _hs
from repro.kernels import scatter_add as _sa
from repro.kernels import zen_commit as _zc
from repro.kernels import zen_encode as _ze

LANES = _hs.LANES
BITS = _bm.BITS


def default_interpret() -> bool:
    """Pallas interpret mode default: real kernels on TPU, interpret (jax-op
    emulation, a correctness vehicle) everywhere else."""
    return jax.default_backend() != "tpu"


def _resolve(interpret):
    return default_interpret() if interpret is None else interpret


def hash_stage_op(indices: jnp.ndarray, seeds, n: int, r1: int,
                  *, interpret: bool | None = None):
    """indices int32 [C] -> (p [C], q [k, C]) via the Pallas kernel."""
    interpret = _resolve(interpret)
    seeds = tuple(int(s) for s in seeds)
    C = indices.shape[0]
    pad = (-C) % (LANES * _hs.BLOCK_ROWS)
    idx2 = jnp.pad(indices, (0, pad), constant_values=EMPTY)
    idx2 = idx2.reshape(-1, LANES)
    p, q = _hs.hash_stage(idx2, seeds=seeds, n=n, r1=r1, interpret=interpret)
    return p.reshape(-1)[:C], q.reshape(len(seeds) - 1, -1)[:, :C]


def bitmap_pack_op(mask: jnp.ndarray, *, interpret: bool | None = None):
    """bool/int [M] -> uint32 [ceil(M/32)] packed words."""
    interpret = _resolve(interpret)
    M = mask.shape[0]
    W = -(-M // BITS)
    padW = (-W) % _bm.BLOCK_W
    bits = jnp.pad(mask.astype(jnp.int32), (0, (W + padW) * BITS - M))
    words = _bm.bitmap_pack(bits.reshape(-1, BITS), interpret=interpret)
    return words[:W]


def bitmap_unpack_op(words: jnp.ndarray, length: int, *,
                     interpret: bool | None = None):
    """uint32 [W] -> bool [length]."""
    interpret = _resolve(interpret)
    W = words.shape[0]
    padW = (-W) % _bm.BLOCK_W
    wp = jnp.pad(words, (0, padW))
    bits = _bm.bitmap_unpack(wp, interpret=interpret)
    return bits.reshape(-1)[:length].astype(bool)


def row_compact_op(mem: jnp.ndarray, *, interpret: bool | None = None):
    """int32 [R, L] -> [R, L] live entries compacted to the front (slot order
    preserved), EMPTY-padded tail."""
    interpret = _resolve(interpret)
    R, L = mem.shape
    pad = (-L) % _cp.BLOCK_J
    memp = jnp.pad(mem, ((0, 0), (0, pad)), constant_values=EMPTY)
    return _cp.row_compact(memp, interpret=interpret)[:, :L]


def coo_scatter_add_op(out: jnp.ndarray, idx: jnp.ndarray, vals: jnp.ndarray,
                       *, interpret: bool | None = None):
    """out [M, d] += vals [C, d] at row idx [C] (EMPTY dropped)."""
    interpret = _resolve(interpret)
    return _sa.coo_scatter_add(out, idx, vals, interpret=interpret)


def batched_coo_reduce_op(out: jnp.ndarray, idx: jnp.ndarray,
                          vals: jnp.ndarray, *, backend: str = "xla",
                          interpret: bool | None = None):
    """One batched segment-reduce for every scheme's server aggregation:
    per-peer COO segments ``idx [n, C]`` / ``vals [n, C(, d)]`` (or
    already-flat) scatter-added into a dense accumulator ``out [M(, d)]``.
    EMPTY and out-of-range indices are dropped.

    This is the shared aggregation primitive of agsparse / sparse_ps /
    balanced / zen (``core/schemes.py`` routes all of them through it).
    ``backend="xla"`` is the flattened ``.at[].add`` every scheme used
    before the hoist — bit-identical updates in identical order;
    ``backend="pallas"`` routes through the sequential-grid RMW kernel
    (kernels/scatter_add.py), widening 1-D values to 2-D for it."""
    idx = idx.reshape(-1)
    vals = vals.reshape(idx.shape[0], *out.shape[1:])
    if backend != "pallas":
        tgt = jnp.where(idx == EMPTY, out.shape[0], idx)
        return out.at[tgt].add(vals, mode="drop")
    squeeze = out.ndim == 1
    out2 = out[:, None] if squeeze else out
    vals2 = vals[:, None] if squeeze else vals
    res = coo_scatter_add_op(out2, idx, vals2, interpret=interpret)
    return res[:, 0] if squeeze else res


def bitmap_pack_rows_op(mask: jnp.ndarray, *, interpret: bool | None = None):
    """bool/int [n, L] -> uint32 [n, ceil(L/32)]: per-row packed occupancy.

    Rows are padded to a word boundary before flattening, so each row's
    words are exactly the 1-D ``bitmap_pack_op`` of that row.
    """
    n, L = mask.shape
    W = -(-L // BITS)
    m = jnp.pad(mask.astype(jnp.int32), ((0, 0), (0, W * BITS - L)))
    return bitmap_pack_op(m.reshape(-1), interpret=interpret).reshape(n, W)


@functools.partial(jax.jit, static_argnames=("seeds", "n", "r1", "r2"))
def _zen_encode_fused_xla(indices: jnp.ndarray, seeds: tuple, n: int,
                          r1: int, r2: int):
    """Single-dispatch XLA composition of the fused encode — hash,
    insertion rounds, extraction, and bitmap pack in ONE executable, no
    intermediate dispatch or HBM round-trip at the jax level.

    Bit-exact re-derivation of ``hierarchical_hash`` + ``row_compact`` +
    pack, tuned for CPU/GPU XLA where scatter cost is a per-update loop:

    * nnz-adaptive lane budget: ``compact_indices`` places every live
      index before the EMPTY pad, and EMPTY candidates can never win a
      slot, take a serial rank, or overflow — so a ``lax.switch`` over
      static slice sizes {cap, cap/2, cap/4} processes only the smallest
      prefix covering the live count.  At d=0.01 with the standard 4x
      capacity margin that is 4x fewer scatter updates per round.
    * serial ranks from a transposed segmented cumsum ([n, C], contiguous
      along the scan axis) instead of ``partition_rank``'s [C, n] layout.
    * gather-only extraction: binary search over each row's validity
      cumsum replaces the compaction scatter, and the occupancy bitmap is
      a prefix-of-nnz mask packed with shifts (no scatter, no sort).
    """
    row = r1 + r2
    cap = indices.shape[0]
    sd = jnp.asarray(seeds, dtype=jnp.uint32)
    from repro.core.hashing import hash_mod

    def pipeline(idxs):
        C = idxs.shape[0]
        valid = idxs != EMPTY
        p = jnp.clip(hash_mod(idxs, sd[0], n), 0, n - 1)
        base = p * row
        mem = jnp.full((n * row,), EMPTY, dtype=jnp.int32)
        pending = valid
        for i in range(1, len(seeds)):
            slot = base + jnp.clip(hash_mod(idxs, sd[i], r1), 0, r1 - 1)
            occupied = mem[slot] != EMPTY
            propose = pending & ~occupied
            cand = jnp.where(propose, idxs, EMPTY)
            mem = mem.at[slot].min(cand, mode="drop")
            won = pending & (mem[slot] == idxs) & propose
            pending = pending & ~won
        surv = pending
        onehot = (p[None, :] == jnp.arange(n, dtype=p.dtype)[:, None]) \
            & surv[None, :]
        seg = jnp.cumsum(onehot.astype(jnp.int32), axis=1) - 1
        rank = jnp.where(surv, seg[p, jnp.arange(C)], -1)
        fits = surv & (rank < r2)
        slot = base + r1 + jnp.clip(rank, 0, r2 - 1)
        mem = mem.at[jnp.where(fits, slot, n * row)].set(
            jnp.where(fits, idxs, EMPTY), mode="drop")
        overflow = jnp.sum((surv & ~fits).astype(jnp.int32))
        mem = mem.reshape(n, row)
        v = mem != EMPTY
        cum = jnp.cumsum(v.astype(jnp.int32), axis=1)
        nnz_row = cum[:, -1:]
        q = jnp.arange(1, row + 1, dtype=jnp.int32)[None, :]
        lo = jnp.zeros((n, row), jnp.int32)
        hi = jnp.full((n, row), row - 1, jnp.int32)
        for _ in range(max(row - 1, 1).bit_length()):
            mid = (lo + hi) // 2
            go_right = jnp.take_along_axis(cum, mid, axis=1) < q
            lo = jnp.where(go_right, mid + 1, lo)
            hi = jnp.where(go_right, hi, mid)
        src = jnp.clip(lo, 0, row - 1)
        j = jnp.arange(row, dtype=jnp.int32)[None, :]
        live = j < nnz_row
        pidx = jnp.where(live, jnp.take_along_axis(mem, src, axis=1), EMPTY)
        W = -(-row // BITS)
        bit = jnp.pad(live.astype(jnp.uint32),
                      ((0, 0), (0, W * BITS - row))).reshape(n, W, BITS)
        occ = jnp.sum(bit << jnp.arange(BITS, dtype=jnp.uint32)[None, None, :],
                      axis=2, dtype=jnp.uint32)
        return pidx, occ, overflow

    sizes = sorted({cap, -(-cap // 2), -(-cap // 4)}, reverse=True)
    if len(sizes) == 1:
        return pipeline(indices)
    nnz = jnp.sum((indices != EMPTY).astype(jnp.int32))
    bidx = sum((nnz <= s).astype(jnp.int32) for s in sizes[1:])
    return jax.lax.switch(
        bidx, [lambda x, s=s: pipeline(jax.lax.slice(x, (0,), (s,)))
               for s in sizes], indices)


def zen_encode_fused_op(indices: jnp.ndarray, seeds, n: int, r1: int,
                        r2: int, *, interpret: bool | None = None,
                        force_kernel: bool = False):
    """Fused Zen encode: ONE dispatch for hash + insertion rounds +
    extraction + bitmap pack (DESIGN.md §11).

    indices int32 [C] (EMPTY-padded, from ``compact_indices``) ->
    (pidx int32 [n, r1+r2], occ uint32 [n, ceil((r1+r2)/32)], overflow
    scalar).  Bit-exact vs ``zen_encode_unfused`` (the 3-dispatch route)
    and ``ref.zen_encode_ref`` (pure-XLA composition) — the CI
    kernel-parity matrix enforces it.

    Dispatch: on TPU (interpret=False) this is the Pallas megakernel in
    ``kernels/zen_encode.py``.  Off-TPU the megakernel's interpret-mode
    emulation would execute its dense hit matrices as real XLA ops —
    O(n·C·L) work that exists only to vectorize the TPU VPU — so the
    fused op lowers to the equivalent single-dispatch XLA composition
    instead (same outputs, one executable).  ``force_kernel=True`` runs
    the interpret-mode megakernel anyway (the parity tests' middle
    oracle: fused kernel → interpret kernel → XLA composition).
    """
    interpret = _resolve(interpret)
    seeds = tuple(int(s) for s in seeds)
    if interpret and not force_kernel:
        return _zen_encode_fused_xla(indices, seeds, n, r1, r2)
    C = indices.shape[0]
    pad = (-C) % LANES
    idx2 = jnp.pad(indices, (0, pad), constant_values=EMPTY)[None, :]
    pidx, occ, ovf = _ze.zen_encode_fused(
        idx2, seeds=seeds, n=n, r1=r1, r2=r2, interpret=interpret)
    L = r1 + r2
    W = -(-L // BITS)
    # nnz per row <= L, so the dropped tail words/columns are all-zero/EMPTY
    return pidx[:, :L], occ[:, :W], jnp.sum(ovf)


@functools.partial(jax.jit, static_argnames=("cap_server", "cap_pull"))
def _zen_commit_push_fused_xla(lp: jnp.ndarray, vals: jnp.ndarray,
                               cap_server: int, cap_pull: int):
    """Single-dispatch XLA composition of the fused commit push —
    aggregation, mask/compaction, value gather and bitmap pack in ONE
    executable.  The scatter-add is the identical flattened ``.at[].add``
    the unfused route lowers (same updates, same order — bitwise equal by
    construction), compaction is ``compact_indices`` and the pack is the
    ``formats.bitmap_encode`` weight-sum, so every word matches the
    3-dispatch chain."""
    from repro.core.hashing import compact_indices

    buf = jnp.zeros((cap_server, vals.shape[-1]), vals.dtype)
    buf = buf.at[lp].add(vals, mode="drop")
    mask = jnp.any(buf != 0, axis=-1)
    lpos, overflow = compact_indices(mask, cap_pull)
    safe = jnp.where(lpos == EMPTY, 0, lpos)
    out = jnp.where((lpos == EMPTY)[:, None], 0, buf[safe])
    pad = (-cap_server) % BITS
    bits = jnp.pad(mask.astype(jnp.uint32), (0, pad)).reshape(-1, BITS)
    weights = jnp.uint32(1) << jnp.arange(BITS, dtype=jnp.uint32)
    bm = jnp.sum(bits * weights, axis=1, dtype=jnp.uint32)
    return lpos, out, bm, overflow


def zen_commit_push_fused_op(lp: jnp.ndarray, vals: jnp.ndarray, *,
                             cap_server: int, cap_pull: int,
                             interpret: bool | None = None,
                             force_kernel: bool = False):
    """Fused Zen commit push: ONE dispatch for server aggregation + mask
    compaction + value gather + bitmap pack (DESIGN.md §14).

    lp int32 [C] server-local positions (EMPTY / >= cap_server dropped),
    vals [C(, d)] pushed values -> (lpos int32 [cap_pull], vals
    [cap_pull(, d)], bm uint32 [ceil(cap_server/32)], overflow scalar).
    Bit-exact vs ``zen_commit_push_unfused`` (the 3-dispatch chain) and
    ``ref.zen_commit_push_ref`` — the CI kernel-parity matrix enforces it.

    Dispatch mirrors ``zen_encode_fused_op``: the Pallas megakernel
    (kernels/zen_commit.py) on TPU, the equivalent single-dispatch XLA
    composition off-TPU (interpret-mode hit matrices are real XLA work
    that exists only to vectorize the VPU), ``force_kernel=True`` for the
    interpret-mode megakernel (the parity tests' middle oracle)."""
    interpret = _resolve(interpret)
    squeeze = vals.ndim == 1
    vals2 = vals[:, None] if squeeze else vals
    if interpret and not force_kernel:
        lpos, v, bm, ov = _zen_commit_push_fused_xla(
            lp, vals2, cap_server, cap_pull)
    else:
        lpos, v, bm, ov = _zc.zen_commit_push_fused(
            lp, vals2, cap_server=cap_server, cap_pull=cap_pull,
            interpret=interpret)
        W = -(-cap_server // BITS)
        lpos, v = lpos[0, :cap_pull], v[:cap_pull]
        bm, ov = bm[0, :W], ov[0, 0]
    return lpos, (v[:, 0] if squeeze else v), bm, ov


def zen_commit_push_unfused(lp: jnp.ndarray, vals: jnp.ndarray, *,
                            cap_server: int, cap_pull: int,
                            interpret: bool | None = None):
    """The pre-fusion commit-push dispatch chain: scatter-add kernel + XLA
    compaction/gather + bitmap-pack kernel.  Kept as the fused
    megakernel's oracle and the benchmark baseline
    (benchmarks/micro_sync.py ``commit_fused`` series)."""
    from repro.core.hashing import compact_indices

    squeeze = vals.ndim == 1
    vals2 = vals[:, None] if squeeze else vals
    buf = coo_scatter_add_op(
        jnp.zeros((cap_server, vals2.shape[-1]), vals2.dtype), lp, vals2,
        interpret=interpret)
    mask = jnp.any(buf != 0, axis=-1)
    lpos, overflow = compact_indices(mask, cap_pull)
    safe = jnp.where(lpos == EMPTY, 0, lpos)
    out = jnp.where((lpos == EMPTY)[:, None], 0, buf[safe])
    bm = bitmap_pack_op(mask, interpret=interpret)
    return lpos, (out[:, 0] if squeeze else out), bm, overflow


@functools.partial(jax.jit, static_argnames=("cap_server", "cap_pull"))
def _zen_commit_pull_fused_xla(words: jnp.ndarray, cap_server: int,
                               cap_pull: int):
    """Single-dispatch XLA composition of the fused pull decode: batched
    bitmap unpack + row compaction (the ``bitmap_decode_batch`` +
    ``compact_rows`` formulations, in one executable)."""
    from repro.core.hashing import compact_rows

    weights = jnp.uint32(1) << jnp.arange(BITS, dtype=jnp.uint32)
    bits = (words[:, :, None] & weights[None, None, :]) != 0
    m = bits.reshape(words.shape[0], -1)[:, :cap_server]
    return compact_rows(m, cap_pull)[0]


def zen_commit_pull_fused_op(words: jnp.ndarray, cap_server: int,
                             cap_pull: int, *,
                             interpret: bool | None = None,
                             force_kernel: bool = False):
    """Fused Zen pull decode: every gathered server bitmap unpacked and
    compacted in one dispatch.  words uint32 [n, W] -> lpos int32
    [n, cap_pull] (set-bit positions below ``cap_server``, ascending,
    EMPTY-padded).  Dispatch as ``zen_commit_push_fused_op``."""
    interpret = _resolve(interpret)
    if interpret and not force_kernel:
        return _zen_commit_pull_fused_xla(words, cap_server, cap_pull)
    n, W = words.shape
    padW = (-W) % (LANES // BITS)  # pad so each row spans whole lanes
    wp = jnp.pad(words, ((0, 0), (0, padW)))
    lpos = _zc.zen_commit_pull_fused(
        wp, cap_server=cap_server, cap_pull=cap_pull, interpret=interpret)
    return lpos[:, :cap_pull]


def zen_commit_pull_unfused(words: jnp.ndarray, cap_server: int,
                            cap_pull: int, *,
                            interpret: bool | None = None):
    """The pre-fusion pull decode: bitmap-unpack kernel + XLA row
    compaction (the fused pull kernel's oracle and bench baseline)."""
    from repro.core.hashing import compact_rows

    n, W = words.shape
    bits = bitmap_unpack_op(words.reshape(-1), n * W * BITS,
                            interpret=interpret)
    m = bits.reshape(n, W * BITS)[:, :cap_server]
    return compact_rows(m, cap_pull)[0]


def zen_encode_unfused(indices: jnp.ndarray, seeds, n: int, r1: int,
                       r2: int, *, interpret: bool | None = None):
    """The pre-fusion 3-dispatch encode: hash_stage kernel + XLA conflict
    rounds + row_compact kernel + bitmap_pack kernel.  Kept as the fused
    megakernel's interpret-mode oracle and the benchmark baseline
    (benchmarks/micro_sync.py ``encode_fused`` series)."""
    from repro.core.hashing import hierarchical_hash  # deferred: cycle

    seeds = tuple(int(s) for s in seeds)
    part = hierarchical_hash(
        indices, n=n, r1=r1, r2=r2, k=len(seeds) - 1, backend="pallas",
        interpret=interpret, static_seeds=seeds)
    pidx = row_compact_op(part.memory, interpret=interpret)
    occ = bitmap_pack_rows_op(pidx != EMPTY, interpret=interpret)
    return pidx, occ, part.overflow
