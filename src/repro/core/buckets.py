"""Gradient-pytree bucketing for overlap-scheduled synchronization.

DESIGN.md §7.  A ``BucketPlan`` partitions the flattened gradient pytree
into fixed-byte **buckets**, the unit at which the trainer emits sync ops
(`repro.train.schedule`):

* **Dense leaves** are flattened and fused: consecutive leaves of the same
  dtype are packed into one bucket while the bucket stays under
  ``bucket_bytes`` (a single leaf larger than the budget becomes its own
  oversized bucket — leaves are never split, so reassembly is a static
  slice/reshape).  One fused ``psum`` per bucket replaces one ``psum`` per
  leaf; because ``psum`` is elementwise, fusion is bit-exact.
* **Row-sparse leaves** (Zen's subject) are *never* fused or split: each is
  its own bucket.  The Zen layout (hash partitions, server offsets,
  bitmap width) is a pure function of the whole tensor's row count —
  splitting a table across buckets would need per-fragment layouts and
  would break the balanced-partition guarantee of Thm. 2 (DESIGN.md §7).
* ``bucket_bytes=None`` is the **monolithic fallback**: one bucket per
  leaf, no fusion — op-for-op the pre-bucketing gradient path, so every
  scheme stays bit-compatible with the PR-1 trainer.

The plan is built offline from abstract shapes (like ``ZenLayout``); the
traced work per step is only ``gather_bucket`` / ``scatter_bucket``
(concat + slice/reshape) around each bucket's sync op.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.schemes import SyncStats
from repro.core.topology import parse_plan

DENSE = "dense_fused"
SPARSE = "sparse"


def _all_dense(tag: str) -> bool:
    """Whether a plan tag moves only psum traffic: the bare 'dense' tag,
    or a hier plan whose every stage is dense — those buckets' words
    belong in ``sync/dense_words`` no matter the topology, so the
    dense/sparse volume split means the same thing at every node_size."""
    if tag == "dense":
        return True
    if tag.startswith("hier("):
        try:
            return all(s.scheme == "dense" for s in parse_plan(tag).stages)
        except ValueError:
            return False
    return False


@dataclasses.dataclass(frozen=True)
class LeafSlot:
    """One gradient leaf's home inside a bucket payload."""

    name: str            # '/'-joined tree path (GradSync naming)
    index: int           # position in jax.tree flatten order
    shape: tuple         # original leaf shape
    dtype: Any
    offset: int          # element offset inside the fused flat payload
    size: int            # element count


@dataclasses.dataclass(frozen=True)
class Bucket:
    """A unit of synchronization: one collective chain per bucket."""

    bid: int
    kind: str                     # DENSE | SPARSE
    # Resolved CommPlan tag (core/topology.py grammar).  On a flat
    # topology this is the bare scheme name — byte-identical to the
    # pre-topology tags; on a hierarchical topology 'auto' resolves to
    # tags like 'hier(zen@intra,dense@inter)' while explicit schemes
    # keep their bare name (expanded per-level at commit time).
    scheme: str
    slots: tuple[LeafSlot, ...]   # exactly 1 slot when kind == SPARSE
    nbytes: int
    # Compressor tag (core/sparsify.py spec string, e.g. 'topk:0.01') for
    # dense buckets whose payload is EF-sparsified before sync; 'none'
    # otherwise.  Row-sparse buckets are never compressed — they arrive
    # sparse, and the Zen layout already budgets their density.
    compress: str = "none"

    @property
    def size(self) -> int:
        return sum(s.size for s in self.slots)

    @property
    def key(self) -> str:
        """Stable identity for per-bucket state (EF residuals, density
        EMAs, Zen layouts): the first slot's leaf path.  Bucket
        *boundaries* depend only on shapes/dtypes/bucket_bytes — never on
        schemes, profiles, or the compressor — so keys survive replans."""
        return self.slots[0].name


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Offline partition of a gradient pytree into sync buckets."""

    buckets: tuple[Bucket, ...]
    n_leaves: int
    bucket_bytes: int | None

    @property
    def schemes(self) -> tuple[str, ...]:
        return tuple(b.scheme for b in self.buckets)

    def counts(self) -> dict[str, int]:
        """The plan's bucket counts: ``n_buckets``, ``compressed_buckets``
        and ``buckets[<scheme>]`` per resolved scheme.  Facts of the plan,
        fixed before the first step, so no step reports them."""
        out = {"n_buckets": len(self.buckets),
               "compressed_buckets": sum(b.compress != "none"
                                         for b in self.buckets)}
        for scheme in sorted(set(self.schemes)):
            out[f"buckets[{scheme}]"] = self.schemes.count(scheme)
        return out

    def validate(self) -> None:
        """Every leaf in exactly one bucket; sparse buckets are singletons;
        fused dense buckets respect the byte budget (oversized leaves may
        stand alone)."""
        seen: set[int] = set()
        for b in self.buckets:
            for s in b.slots:
                if s.index in seen:
                    raise ValueError(f"leaf {s.name} assigned twice")
                seen.add(s.index)
            if b.kind == SPARSE and len(b.slots) != 1:
                raise ValueError(f"sparse bucket {b.bid} fuses leaves")
            if b.kind == SPARSE and b.compress != "none":
                raise ValueError(
                    f"row-sparse bucket {b.bid} must not be compressed")
            if (self.bucket_bytes is not None and b.kind == DENSE
                    and len(b.slots) > 1 and b.nbytes > self.bucket_bytes):
                raise ValueError(
                    f"fused bucket {b.bid} exceeds bucket_bytes")
        if len(seen) != self.n_leaves:
            raise ValueError(
                f"plan covers {len(seen)} of {self.n_leaves} leaves")


def leaf_path_str(path) -> str:
    return "/".join(
        str(getattr(p, "key", getattr(p, "idx", p))) for p in path
    )


def _leaf_nbytes(leaf) -> int:
    return int(leaf.size) * jnp.dtype(leaf.dtype).itemsize


def make_bucket_plan(
    grad_shapes: Any,
    is_sparse: Callable[[str], bool],
    bucket_bytes: int | None,
    sparse_scheme: Callable[[str, Any], str],
    dense_scheme: str = "dense",
    compress: str = "none",
    compressed_scheme: Callable[[str, int], str] | None = None,
) -> BucketPlan:
    """Build the plan from abstract grad shapes (offline, untraced).

    ``sparse_scheme(name, leaf)`` resolves the per-tensor scheme for a
    row-sparse leaf (the 'auto' cost-model decision lives in the caller);
    dense buckets use ``dense_scheme`` — unless ``compress`` is a
    sparsifier tag (core/sparsify.py), in which case every dense bucket
    is tagged with it and its scheme comes from
    ``compressed_scheme(key, size)`` (the caller's cost-model decision on
    the *post-compression* density profile).
    """
    if bucket_bytes is not None and bucket_bytes <= 0:
        raise ValueError(f"bucket_bytes must be positive, got {bucket_bytes}")
    leaves = jax.tree_util.tree_flatten_with_path(grad_shapes)[0]
    buckets: list[Bucket] = []
    pend: list[LeafSlot] = []   # dense leaves awaiting fusion
    pend_bytes = 0

    def flush():
        nonlocal pend, pend_bytes
        if pend:
            scheme = dense_scheme
            if compress != "none" and compressed_scheme is not None:
                scheme = compressed_scheme(
                    pend[0].name, sum(s.size for s in pend))
            buckets.append(Bucket(
                bid=len(buckets), kind=DENSE, scheme=scheme,
                slots=tuple(pend), nbytes=pend_bytes, compress=compress))
            pend, pend_bytes = [], 0

    for i, (path, leaf) in enumerate(leaves):
        name = leaf_path_str(path)
        size = int(leaf.size)
        nbytes = _leaf_nbytes(leaf)
        if is_sparse(name):
            flush()
            buckets.append(Bucket(
                bid=len(buckets), kind=SPARSE,
                scheme=sparse_scheme(name, leaf),
                slots=(LeafSlot(name, i, tuple(leaf.shape), leaf.dtype,
                                0, size),),
                nbytes=nbytes))
            continue
        fits = (bucket_bytes is not None and pend
                and pend[0].dtype == leaf.dtype
                and pend_bytes + nbytes <= bucket_bytes)
        if not fits:
            flush()
        pend.append(LeafSlot(
            name, i, tuple(leaf.shape), leaf.dtype,
            offset=sum(s.size for s in pend), size=size))
        pend_bytes += nbytes
        if bucket_bytes is None or pend_bytes >= bucket_bytes:
            flush()
    flush()
    plan = BucketPlan(buckets=tuple(buckets), n_leaves=len(leaves),
                      bucket_bytes=bucket_bytes)
    plan.validate()
    return plan


# ---------------------------------------------------------------------------
# payload assembly / disassembly (the only traced code in this module)
# ---------------------------------------------------------------------------

def gather_bucket(bucket: Bucket, flat_leaves: list) -> jnp.ndarray:
    """Assemble a bucket's payload from the flat leaf list.

    Sparse buckets pass their single leaf through unchanged (the scheme
    needs the [rows, d] structure); dense buckets are a flat concat."""
    if bucket.kind == SPARSE:
        return flat_leaves[bucket.slots[0].index]
    parts = [flat_leaves[s.index].reshape(-1) for s in bucket.slots]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def scatter_bucket(bucket: Bucket, payload: jnp.ndarray, out: list) -> None:
    """Write a synced payload back into the flat leaf list ``out``."""
    if bucket.kind == SPARSE:
        out[bucket.slots[0].index] = payload
        return
    for s in bucket.slots:
        out[s.index] = payload[s.offset:s.offset + s.size].reshape(s.shape)


# ---------------------------------------------------------------------------
# SyncStats reduction across buckets
# ---------------------------------------------------------------------------

def reduce_stats(
    plan: BucketPlan, per_bucket: list[SyncStats],
    extra: dict[str, jnp.ndarray] | None = None,
) -> dict[str, jnp.ndarray]:
    """Reduce per-bucket SyncStats into the trainer's metric dict.

    Keeps the monolithic path's keys (sparse_sent_words / overflow /
    dense_words) so dashboards and the multi-device tests are unchanged;
    the bucket counts are facts of the plan (``BucketPlan.counts``), not
    of the step, and are not reported here.  ``dense_words``
    counts the fused-psum buckets; everything synchronized with a sparse
    scheme — row-sparse leaves AND compressed dense buckets — lands in
    ``sparse_sent_words`` (for uncompressed plans the split is identical
    to the historical by-kind accounting, because dense buckets always
    carried scheme='dense' there).  ``extra`` merges caller-supplied
    per-bucket metrics (e.g. the EF density measurements)."""
    sent = jnp.float32(0.0)
    dense_words = jnp.float32(0.0)
    overflow = jnp.int32(0)
    level_words: list = []
    for b, st in zip(plan.buckets, per_bucket):
        overflow = overflow + st.overflow
        if b.kind == SPARSE or not _all_dense(b.scheme):
            sent = sent + st.sent_words
        else:
            dense_words = dense_words + st.sent_words
        # hierarchical plans tag wire words by topology level (fastest
        # first); accumulate a whole-step per-level split
        for i, w in enumerate(getattr(st, "by_level", ()) or ()):
            while len(level_words) <= i:
                level_words.append(jnp.float32(0.0))
            level_words[i] = level_words[i] + w
    stats = {
        "sync/sparse_sent_words": sent,
        "sync/overflow": overflow,
        "sync/dense_words": dense_words,
    }
    if len(level_words) >= 2:
        stats["sync/intra_words"] = level_words[0]
        stats["sync/inter_words"] = level_words[-1]
    stats.update(extra or {})
    return stats
