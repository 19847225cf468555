"""Every Pallas kernel of the ``backend="pallas"`` route compiles for a TPU
v5e, at micro_sync's gate point (M=2**14, n=4, d=0.01; flat values and
rows of 128).

The chip is described, not attached (``jax.experimental.topologies``):
Mosaic compiles each kernel exactly as it would for the chip and refuses
what the chip would refuse (unaligned blocks, unsupported primitives,
more VMEM than a kernel may use), which interpret mode cannot show.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or in ``parametrize`` — so that every pytest
worker collects the same tests and only the worker running this file
loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import schemes
from repro.kernels import ops

M = 1 << 14
N = 4
DENSITY = 0.01
ROW_D = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def layout():
    return schemes.make_zen_layout(M, N, density_budget=4 * DENSITY)


def _compile(sharding, fn, *shapes):
    """Compile ``fn`` for the described chip; return its optimized HLO."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo, "no Pallas kernel in the compiled HLO"
    return hlo


def _vshape(*lead, d):
    return (*lead, d) if d else tuple(lead)


def test_hash_stage_compiles(one_chip, layout):
    lo = layout
    _compile(one_chip, lambda i: ops.hash_stage_op(
        i, lo.static_seeds(), lo.n, lo.r1, interpret=False),
        ((lo.cap_index,), jnp.int32))


def test_bitmap_pack_unpack_compile(one_chip):
    W = -(-M // 32)
    _compile(one_chip, lambda m: ops.bitmap_pack_op(m, interpret=False),
             ((M,), jnp.bool_))
    _compile(one_chip,
             lambda w: ops.bitmap_unpack_op(w, M, interpret=False),
             ((W,), jnp.uint32))


def test_row_compact_compiles(one_chip, layout):
    lo = layout
    _compile(one_chip, lambda m: ops.row_compact_op(m, interpret=False),
             ((lo.n, lo.r1 + lo.r2), jnp.int32))


def test_zen_encode_fused_compiles(one_chip, layout):
    lo = layout
    _compile(one_chip, lambda i: ops.zen_encode_fused_op(
        i, lo.static_seeds(), lo.n, lo.r1, lo.r2, interpret=False),
        ((lo.cap_index,), jnp.int32))


@pytest.mark.parametrize("d", [0, ROW_D])
def test_zen_commit_push_fused_compiles(one_chip, layout, d):
    lo = layout
    C = lo.n * (lo.r1 + lo.r2)
    _compile(one_chip, lambda lp, v: ops.zen_commit_push_fused_op(
        lp, v, cap_server=lo.cap_server, cap_pull=lo.r1 + lo.r2,
        interpret=False),
        ((C,), jnp.int32), (_vshape(C, d=d), jnp.float32))


def test_zen_commit_pull_fused_compiles(one_chip, layout):
    lo = layout
    W = -(-lo.cap_server // 32)
    _compile(one_chip, lambda w: ops.zen_commit_pull_fused_op(
        w, lo.cap_server, lo.r1 + lo.r2, interpret=False),
        ((lo.n, W), jnp.uint32))


@pytest.mark.parametrize("d", [0, ROW_D])
def test_batched_coo_reduce_compiles(one_chip, layout, d):
    C = layout.cap_index
    _compile(one_chip, lambda o, i, v: ops.batched_coo_reduce_op(
        o, i, v, backend="pallas", interpret=False),
        (_vshape(M, d=d), jnp.float32), ((N, C), jnp.int32),
        (_vshape(N, C, d=d), jnp.float32))


@pytest.mark.parametrize("fused", [True, False], ids=["fused", "unfused"])
@pytest.mark.parametrize("d", [0, ROW_D])
def test_zen_sync_pallas_route_compiles(one_chip, layout, d, fused):
    """The whole simulated zen_sync on the pallas route — the call
    chip_smoke.py runs — with the fused megakernels (the default) and
    with the pre-fusion chain (hash stage, row compaction, bitmap
    pack/unpack, scatter-add)."""
    def run(g):
        return schemes.simulate(
            schemes.zen_sync, g, layout=layout, backend="pallas",
            interpret=False, fused=fused, fused_commit=fused)

    hlo = _compile(one_chip, run, (_vshape(N, M, d=d), jnp.float32))
    n_kernels = hlo.count("custom_call_target=\"tpu_custom_call\"")
    assert n_kernels >= (3 if fused else 5), n_kernels
