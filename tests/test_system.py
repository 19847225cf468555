"""End-to-end behaviour tests for the training system."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.zen import SyncConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.mesh import make_mesh
from repro.optim.optimizers import OptConfig
from repro.train.build import attach_train, build_program
from repro.train.steps import TrainerConfig


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1, 1), ("data", "model"))


def _run(cfg, mesh, tcfg, steps, seq=32, batch=4, seed=0,
         with_metrics=False):
    prog = build_program(cfg, mesh, tcfg)
    attach_train(prog, seq_len=seq, global_batch=batch)
    params = prog.init_params(seed)
    opt = prog.init_opt(params)
    data = iter(SyntheticLM(cfg, DataConfig(seq_len=seq, batch=batch)))
    losses = []
    for _ in range(steps):
        b = next(data)
        batch_j = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt, m = prog.train_step(params, opt, batch_j)
        losses.append(float(m["loss"]))
    if with_metrics:
        sync_m = {k: float(v) for k, v in m.items() if k.startswith("sync/")}
        sync_m["plan"] = prog.gradsync.plan.counts()
        return losses, params, sync_m
    return losses, params


def test_loss_decreases(mesh):
    cfg = get_config("qwen2-0.5b").reduced()
    tcfg = TrainerConfig(opt=OptConfig(lr=1e-3), sync=SyncConfig())
    losses, _ = _run(cfg, mesh, tcfg, steps=12)
    assert losses[-1] < losses[0] - 0.5, losses
    assert all(np.isfinite(losses))


def _zero1_pair(mesh, dtype: str) -> dict:
    """Losses and final params (as f32, which holds bf16 exactly) of 3
    steps with and without ZeRO-1 on ``mesh``."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype=jnp.dtype(dtype))
    out = {}
    for zero1 in (True, False):
        tcfg = TrainerConfig(opt=OptConfig(lr=1e-3), zero1=zero1)
        losses, params = _run(cfg, mesh, tcfg, steps=3)
        out[f"{zero1}/loss"] = np.asarray(losses)
        for i, leaf in enumerate(jax.tree.leaves(params)):
            out[f"{zero1}/p{i}"] = np.asarray(leaf, np.float32)
    return out


# Without --xla_allow_excess_precision=false, XLA:CPU may keep a bf16
# intermediate of the gradient path in f32 in one program and round it in
# the other, as its fusions differ: a few elements then move by one bf16
# ulp at dp=4.  With it, each program rounds where its source says, and
# the comparison is exact.
ZERO1_DP4_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4"
                               " --xla_allow_excess_precision=false")
    import numpy as np
    sys.path.insert(0, {tests!r})
    from test_system import _zero1_pair
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 1), ("data", "model"))
    for dtype in ("float32", "bfloat16"):
        np.savez(os.path.join({out!r}, dtype + ".npz"),
                 **_zero1_pair(mesh, dtype))
""")


@pytest.fixture(scope="module")
def zero1_dp4(tmp_path_factory):
    """``_zero1_pair`` at dp=4 on virtual CPU devices, both dtypes, in one
    subprocess (this process keeps its single device)."""
    out = tmp_path_factory.mktemp("zero1_dp4")
    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"))
    script = ZERO1_DP4_WORKER.format(
        tests=os.path.dirname(os.path.abspath(__file__)), out=str(out))
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    return {d: dict(np.load(out / f"{d}.npz"))
            for d in ("float32", "bfloat16")}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("dp", [1, 4])
def test_zero1_equals_full_optimizer(mesh, request, dp, dtype):
    """ZeRO-1's chunked update is the plain update, element for element:
    each rank runs the same AdamW arithmetic on its rows of every leaf,
    so parameters (bf16 ones rounded once) and losses agree bit for bit
    with ``zero1=False`` at any data-parallel degree."""
    if dp == 1:
        runs = _zero1_pair(mesh, dtype)
    else:
        runs = request.getfixturevalue("zero1_dp4")[dtype]
    np.testing.assert_array_equal(runs["True/loss"], runs["False/loss"])
    n = sum(k.startswith("True/p") for k in runs)
    assert n > 0
    for i in range(n):
        np.testing.assert_array_equal(runs[f"True/p{i}"],
                                      runs[f"False/p{i}"], err_msg=f"p{i}")



@pytest.mark.parametrize("zero1", [True, False])
def test_clipped_gradient_has_the_clip_norm(mesh, zero1):
    """A clipped step hands AdamW a gradient whose global norm is the clip
    norm to float32 rounding, in a bf16 model too: the factor is applied
    in float32 (rounded to bf16 it would be off by up to 0.2 %)."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype=jnp.bfloat16)
    ocfg = OptConfig(lr=1e-3, grad_clip=1e-3)
    prog = build_program(cfg, mesh, TrainerConfig(opt=ocfg, zero1=zero1))
    attach_train(prog, seq_len=32, global_batch=4)
    params = prog.init_params(0)
    opt = prog.init_opt(params)
    b = next(iter(SyntheticLM(cfg, DataConfig(seq_len=32, batch=4))))
    _, opt, m = prog.train_step(params, opt,
                                {k: jnp.asarray(v) for k, v in b.items()})
    assert float(m["grad_norm"]) > 10 * ocfg.grad_clip
    # after one step m = (1 - b1) g, in float32
    moments = jax.tree.leaves(opt["leaves"],
                              is_leaf=lambda x: isinstance(x, dict) and "m" in x)
    gn = np.sqrt(sum(float(jnp.sum(jnp.square(s["m"] / (1 - ocfg.b1))))
                     for s in moments))
    assert gn == pytest.approx(ocfg.grad_clip, rel=1e-5)

def test_all_sync_schemes_end_to_end(mesh):
    """Every baseline scheme runs as the trainer's gradient synchronizer
    (the Fig. 11/12 experiment is runnable, not just modeled)."""
    cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                              dtype=jnp.float32)
    ref_losses = None
    for scheme in ["dense", "zen", "agsparse", "sparse_ps", "omnireduce"]:
        tcfg = TrainerConfig(opt=OptConfig(lr=1e-3),
                             sync=SyncConfig(scheme=scheme,
                                             density_budget=0.9))
        losses, _ = _run(cfg, mesh, tcfg, steps=2)
        assert all(np.isfinite(losses)), scheme
        if ref_losses is None:
            ref_losses = losses
        else:
            # all schemes are exact at sufficient capacity -> same losses
            np.testing.assert_allclose(losses, ref_losses, rtol=1e-4,
                                       err_msg=scheme)


def test_checkpoint_roundtrip(tmp_path, mesh):
    from repro.checkpoint.io import restore, save
    cfg = get_config("qwen2-0.5b").reduced()
    prog = build_program(cfg, mesh, TrainerConfig())
    params = prog.init_params(0)
    save(tmp_path / "ckpt", {"params": params, "step": jnp.asarray(3)})
    back = restore(tmp_path / "ckpt")
    assert int(back["step"]) == 3
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(back["params"])):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_data_pipeline_determinism_and_sharding():
    cfg = get_config("qwen2-0.5b").reduced()
    dc = DataConfig(seq_len=16, batch=2, seed=7)
    a = next(iter(SyntheticLM(cfg, dc, shard=0)))
    b = next(iter(SyntheticLM(cfg, dc, shard=0)))
    c = next(iter(SyntheticLM(cfg, dc, shard=1)))
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    assert a["tokens"].max() < cfg.vocab


def test_auto_scheme_selection(mesh):
    """'auto' (beyond-paper): Zen for genuinely sparse leaves, dense
    fallback when the budgeted sparse volume would exceed allreduce."""
    import dataclasses as dc
    cfg = dc.replace(get_config("qwen2-0.5b").reduced(), dtype=jnp.float32)
    # low budget: embedding leaf picks zen.  0.15 provisions the measured
    # ~0.09 batch density with hash-collision headroom — "zen is exact"
    # only holds without §2 overflow, which we assert instead of assuming
    # (an under-provisioned 0.05 budget drops rows for SOME hash seeds)
    t_lo = TrainerConfig(sync=SyncConfig(scheme="auto", density_budget=0.15))
    l1, _, m1 = _run(cfg, mesh, t_lo, steps=2, with_metrics=True)
    assert m1["plan"].get("buckets[zen]", 0) > 0, m1
    assert m1["sync/overflow"] == 0, m1
    # absurd budget: auto must fall back to dense (zen would be larger)
    t_hi = TrainerConfig(sync=SyncConfig(scheme="auto", density_budget=5.0))
    l2, _, m2 = _run(cfg, mesh, t_hi, steps=2, with_metrics=True)
    assert m2["plan"].get("buckets[zen]", 0) == 0, m2
    t_dense = TrainerConfig(sync=SyncConfig(scheme="dense"))
    l3, _ = _run(cfg, mesh, t_dense, steps=2)
    np.testing.assert_allclose(l1, l3, rtol=1e-3)  # zen exact (no overflow)
    np.testing.assert_allclose(l2, l3, rtol=1e-6)  # dense == dense
