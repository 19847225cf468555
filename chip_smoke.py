"""Chip smoke test: the Zen trainer and its Pallas sync kernels on a TPU.

Run from the root of a checkout, on a machine with a TPU:

    python3 chip_smoke.py               # one chip
    python3 chip_smoke.py --four-chip   # the dp=4 phase only, four chips

One chip runs two phases in this one process (it starts no children):

1. Trainer: qwen2-0.5b at its published config (24 layers, d_model 896,
   vocab 151,936), mesh 1x1, ``--sync zen`` on its default route, seq 512,
   global batch 8, three steps through ``build_program`` ->
   ``attach_train`` -> ``train_step``, the calls ``launch/train.py``
   makes.  Every loss must be finite.
2. Pallas sync kernels: ``schemes.simulate(zen_sync, ...)`` with
   ``backend="pallas", interpret=False`` (fused megakernels, and the
   pre-fusion chain) against ``backend="xla"`` at micro_sync's gate point
   (M=2**14, n=4, d=0.01; flat values and rows of 128; dyadic values, so
   any summation order is exact), and ``ops.batched_coo_reduce_op`` the
   same way.  Results must be bit-identical, and the lowered program must
   hold a ``tpu_custom_call`` (a reference cannot stand in for a kernel).

``--four-chip`` runs only the data-parallel path: mesh 4x1, qwen2-0.5b at
full width, global batch 32, three steps under ``zen`` and under
``dense``.  Per-step losses must agree within 1e-3, zen must report no
overflow and a positive sparse wire volume, and the mesh must hold four
distinct devices.

Earlier lines report losses, compile and step seconds and the device;
the last line is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without the repository's ``src/`` beside it, the
script exits non-zero and prints no such line.  Step times printed here
are bring-up observations, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
SEQ = 512
STEPS = 3
GATE_M, GATE_N, GATE_DENSITY, GATE_D = 1 << 14, 4, 0.01, 128


def log(msg: str) -> None:
    print(msg, flush=True)


def train(mesh_shape, global_batch: int, sync: str, *, seed: int = 0):
    """Three steps of the trainer's normal path; returns (losses, last
    metrics, compile seconds, steady seconds per step)."""
    from repro.configs import get_config
    from repro.core.zen import SyncConfig
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_mesh
    from repro.optim.optimizers import OptConfig
    from repro.train.build import attach_train, build_program
    from repro.train.steps import TrainerConfig

    cfg = get_config(ARCH)
    mesh = make_mesh(mesh_shape, ("data", "model"))
    n_dev = len({d.id for d in mesh.devices.flat})
    if n_dev != math.prod(mesh_shape):
        raise SystemExit(f"mesh {mesh_shape} holds {n_dev} distinct devices")
    tcfg = TrainerConfig(opt=OptConfig(lr=3e-4), sync=SyncConfig(scheme=sync))
    prog = build_program(cfg, mesh, tcfg)
    attach_train(prog, SEQ, global_batch)
    params = prog.init_params(seed)
    opt = prog.init_opt(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    log(f"[train] {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"vocab={cfg.vocab} params={n_params:,} mesh={mesh_shape} "
        f"sync={sync} seq={SEQ} global_batch={global_batch}")
    data = SyntheticLM(cfg, DataConfig(seq_len=SEQ, batch=global_batch,
                                       seed=seed))
    losses, step_s, m = [], [], {}
    for step in range(STEPS):
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        t0 = time.perf_counter()
        params, opt, m = prog.train_step(params, opt, batch)
        loss = float(jax.block_until_ready(m["loss"]))
        step_s.append(time.perf_counter() - t0)
        losses.append(loss)
        log(f"[train] step {step} loss={loss:.6f} "
            f"seconds={step_s[-1]:.3f} "
            f"sparse_words={float(m['sync/sparse_sent_words']):.0f} "
            f"overflow={int(m['sync/overflow'])}")
    if not all(math.isfinite(x) for x in losses):
        raise SystemExit(f"non-finite loss: {losses}")
    steady = float(np.mean(step_s[1:]))
    log(f"[train] losses={losses} compile_plus_first_step_s={step_s[0]:.3f} "
        f"steady_step_s={steady:.4f}")
    metrics = {k: float(v) for k, v in m.items()}
    del params, opt
    return losses, metrics, step_s[0], steady


def _dyadic_workers(key, d):
    """Gate-point worker gradients with dyadic values (exact under any
    summation order)."""
    from repro.core import metrics

    masks = metrics.synth_sparse_masks(key, GATE_N, GATE_M, GATE_DENSITY)
    shape = (GATE_N, GATE_M) + ((d,) if d else ())
    vals = jnp.round(jax.random.normal(key, shape) * 256) / 256
    return vals * (masks[..., None] if d else masks)


def _same(name: str, got, want) -> None:
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        if g.shape != w.shape or not np.array_equal(g, w):
            raise SystemExit(f"{name}: pallas and xla differ "
                             f"(max |diff| {np.max(np.abs(g - w))})")


def _kernel_lowered(name: str, fn, *args) -> None:
    if "tpu_custom_call" not in jax.jit(fn).lower(*args).as_text():
        raise SystemExit(f"{name}: no tpu_custom_call in the lowered program")


def pallas_vs_xla() -> None:
    """Phase 2: the Pallas route, compiled for the chip, against XLA."""
    from repro.core import schemes
    from repro.core.hashing import EMPTY
    from repro.kernels import ops

    layout = schemes.make_zen_layout(GATE_M, GATE_N,
                                     density_budget=4 * GATE_DENSITY)
    for d in (0, GATE_D):
        vals = _dyadic_workers(jax.random.PRNGKey(d), d)
        want = jax.jit(lambda g: schemes.simulate(
            schemes.zen_sync, g, layout=layout, backend="xla"))(vals)
        exact = np.asarray(vals.sum(0))
        for fused in (True, False):
            def run(g, fused=fused):
                return schemes.simulate(
                    schemes.zen_sync, g, layout=layout, backend="pallas",
                    interpret=False, fused=fused, fused_commit=fused)

            name = (f"zen_sync d={d or 'flat'} "
                    f"{'fused' if fused else 'unfused'}")
            _kernel_lowered(name, run, vals)
            t0 = time.perf_counter()
            got = jax.block_until_ready(jax.jit(run)(vals))
            _same(name, got, want)
            out, stats = got
            if int(np.max(np.asarray(stats.overflow))) != 0:
                raise SystemExit(f"{name}: overflow at the gate point")
            if not np.array_equal(np.asarray(out)[0], exact):
                raise SystemExit(f"{name}: differs from the exact sum")
            log(f"[pallas] {name}: bit-exact vs xla and the exact sum; "
                f"sent_words/worker={float(np.asarray(stats.sent_words)[0]):.0f} "
                f"first_call_s={time.perf_counter() - t0:.3f}")

        key = jax.random.PRNGKey(100 + d)
        C = layout.cap_index
        idx = jax.random.randint(key, (GATE_N, C), 0, GATE_M, jnp.int32)
        idx = jnp.where(jax.random.uniform(key, (GATE_N, C)) < 0.1, EMPTY, idx)
        v = jnp.round(jax.random.normal(
            key, (GATE_N, C) + ((d,) if d else ())) * 256) / 256
        out0 = jnp.zeros((GATE_M,) + ((d,) if d else ()), jnp.float32)

        def reduce(o, i, x, backend):
            return ops.batched_coo_reduce_op(o, i, x, backend=backend,
                                             interpret=False)

        name = f"batched_coo_reduce d={d or 'flat'}"
        _kernel_lowered(name, lambda o, i, x: reduce(o, i, x, "pallas"),
                        out0, idx, v)
        got = jax.jit(lambda o, i, x: reduce(o, i, x, "pallas"))(out0, idx, v)
        want = jax.jit(lambda o, i, x: reduce(o, i, x, "xla"))(out0, idx, v)
        _same(name, got, want)
        log(f"[pallas] {name}: bit-exact vs xla "
            f"({int(np.sum(np.asarray(idx) != EMPTY))} updates)")


def one_chip() -> None:
    log(f"[device] {jax.devices()[0].device_kind} x{len(jax.devices())}")
    train((1, 1), 8, "zen")
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[train] peak_bytes_in_use={stats['peak_bytes_in_use']}")
    pallas_vs_xla()


def four_chip() -> None:
    if len(jax.devices()) < 4:
        raise SystemExit(f"--four-chip needs 4 devices, found "
                         f"{len(jax.devices())}")
    log(f"[device] {jax.devices()[0].device_kind} x{len(jax.devices())}")
    zen, zm, _, _ = train((4, 1), 32, "zen")
    dense, _, _, _ = train((4, 1), 32, "dense")
    gap = max(abs(a - b) for a, b in zip(zen, dense))
    log(f"[four-chip] zen={zen} dense={dense} max_gap={gap:.3e}")
    if gap >= 1e-3:
        raise SystemExit(f"zen and dense losses differ by {gap}")
    if zm["sync/overflow"] != 0:
        raise SystemExit(f"zen overflowed: {zm['sync/overflow']}")
    if not zm["sync/sparse_sent_words"] > 0:
        raise SystemExit("zen reported no sparse wire traffic at dp=4")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the dp=4 zen-vs-dense trainer phase")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {dev.platform}); nothing run",
              file=sys.stderr)
        raise SystemExit(1)
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[cache] {enable_compile_cache()}")
    four_chip() if args.four_chip else one_chip()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
