"""Training launcher.

Examples:
  # smoke-scale local run (1 device)
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --reduced \\
      --steps 50 --seq-len 128 --global-batch 4 --mesh 1x1

  # production config (real TPU pod; mesh 16x16)
  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b \\
      --steps 1000 --seq-len 4096 --global-batch 256 --mesh 16x16 \\
      --sync zen
"""
from __future__ import annotations

import argparse
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.checkpoint.io import save
from repro.configs import ALL_ARCHS, get_config
from repro.core.registry import cli_scheme_choices
from repro.core.sparsify import DensityController
from repro.core.zen import SyncConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.common import make_ctx
from repro.optim.optimizers import OptConfig
from repro.train.build import attach_train, build_program
from repro.train.steps import TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ALL_ARCHS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1",
                    help="DxM or PxDxM, e.g. 16x16 or 2x16x16")
    ap.add_argument("--sync", default="zen",
                    choices=cli_scheme_choices())
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--density-budget", type=float, default=0.25)
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="bucketed overlap schedule: fuse dense grads into "
                         "buckets of at most this many bytes (DESIGN.md §7)")
    ap.add_argument("--node-size", type=int, default=1,
                    help="devices per node (DESIGN.md §10): splits the "
                         "data axis into nested (dp_inter, dp_intra) mesh "
                         "axes and plans per-bucket two-level CommPlans "
                         "(aggregate intra-node, then cross the slow "
                         "links); must divide the data-parallel degree; "
                         "1 = flat (bit-identical to the pre-topology "
                         "trainer)")
    ap.add_argument("--alpha-beta", default=None,
                    help="α-β link override for the topology cost model: "
                         "'a_intra,b_intra,a_inter,b_inter' (µs, µs per "
                         "f32 word) or 'a,b' for every level; default: "
                         "core/topology.py's ICI/DCN-class constants")
    ap.add_argument("--compress", default="none",
                    help="EF-sparsify dense gradient buckets before sync "
                         "(DESIGN.md §8): 'topk:0.01', 'randk:0.05', "
                         "'threshold:1e-3'; append ':noef' to drop the "
                         "error-feedback residual (lossy)")
    ap.add_argument("--calib-file", default=None,
                    help="measured-time cost calibration (DESIGN.md §11): "
                         "JSON table of per-stage encode/commit/dense "
                         "times; scheme='auto' then only picks zen when "
                         "the wire win survives the MEASURED encode cost. "
                         "Missing file: CostCalibrator runs once on this "
                         "machine and writes it.  Also produced by "
                         "`python -m repro.core.costmodel`")
    ap.add_argument("--no-fused-commit", action="store_true",
                    help="run zen's commit stage as the pre-fusion "
                         "dispatch chain (scatter-add -> compact -> "
                         "bitmap-encode / unpack -> decode) instead of "
                         "the fused push/pull megakernels (DESIGN.md "
                         "§14); bit-identical output, A/B-timing knob")
    ap.add_argument("--replan-every", type=int, default=0,
                    help="adaptive density control: every N steps compare "
                         "choose_scheme on the MEASURED post-compression "
                         "densities against the live plan and rebuild "
                         "(recompile) when a bucket's dense<->zen choice "
                         "flips; 0 = static plan")
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"devices={len(jax.devices())} compile_cache={cache_dir}")
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    dims = [int(x) for x in args.mesh.split("x")]
    axes = ("pod", "data", "model")[-len(dims):]
    # eager §9/§10 validation: reject a tp or node_size that does not
    # divide the config (clear error naming the config) BEFORE jax
    # allocates the mesh
    pods, dp, tp = ([1] * (3 - len(dims)) + dims)
    make_ctx(cfg, tp, dp, pods, node_size=args.node_size)
    mesh = make_mesh(tuple(dims), axes, node_size=args.node_size)
    if args.calib_file and not Path(args.calib_file).exists():
        # calibrate once on this machine, persist, then plan from it
        from repro.core.costmodel import CostCalibrator
        print(f"calibrating encode/commit times -> {args.calib_file}")
        CostCalibrator(n=max(dp, 2), iters=3).measure().save(args.calib_file)
    tcfg = TrainerConfig(
        opt=OptConfig(lr=args.lr),
        sync=SyncConfig(scheme=args.sync,
                        density_budget=args.density_budget,
                        bucket_bytes=args.bucket_bytes,
                        compress=args.compress,
                        alpha_beta=args.alpha_beta,
                        calib_file=args.calib_file,
                        fused_commit=not args.no_fused_commit),
        zero1=not args.no_zero1)
    prog = build_program(cfg, mesh, tcfg)
    attach_train(prog, args.seq_len, args.global_batch)
    params = prog.init_params(args.seed)
    opt = prog.init_opt(params)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params / 1e6:.1f}M mesh={args.mesh} "
          f"sync={args.sync} compress={args.compress} "
          f"node_size={args.node_size}")
    # the plan a run executes is printed, not inferred (DESIGN.md §10)
    for line in prog.gradsync.describe():
        print(f"  {line}")

    # adaptive density control (DESIGN.md §8): measured post-compression
    # densities feed choose_scheme; a dense<->zen flip triggers a replan.
    # Only under scheme='auto': with an explicit scheme the resolver
    # ignores recommendations, so a disagreeing controller would flag
    # drift (and recompile) every interval without ever converging.
    controller = None
    if (args.replan_every and prog.gradsync.has_compression
            and args.sync == "auto"):
        controller = DensityController(
            prog.gradsync.compressed_buckets(),
            prog.gradsync.bucket_schemes(),
            n=prog.model.ctx.dp,
            threshold=tcfg.sync.auto_threshold,
            # hier plans live in the topology's tag space; flat keeps the
            # historical int-n decision (bit-identical picks)
            topology=(None if prog.gradsync.topology.flat
                      else prog.gradsync.topology),
            # replan decisions price encode with the same measured table
            # as the live plan (no calib -> analytic, as before)
            calib=prog.gradsync.calib)

    data = iter(SyntheticLM(cfg, DataConfig(
        seq_len=args.seq_len, batch=args.global_batch, seed=args.seed)))
    # the tok/s window opens after step 0, whose time is mostly the
    # compile; step 0 is timed and reported on its own
    t0 = time.perf_counter()
    tokens_done = 0
    for step in range(args.steps):
        b = next(data)
        batch = {k: jnp.asarray(v) for k, v in b.items()}
        params, opt, m = prog.train_step(params, opt, batch)
        if step == 0:
            jax.block_until_ready(m["loss"])
            print(f"step 0 (compile + first step) "
                  f"{time.perf_counter() - t0:.2f}s")
            t0 = time.perf_counter()
        else:
            tokens_done += args.global_batch * args.seq_len
        if step % args.log_every == 0 or step == args.steps - 1:
            jax.block_until_ready(m["loss"])
            dt = time.perf_counter() - t0
            rate = (f"tok/s={tokens_done / dt:,.0f} " if tokens_done
                    else "")
            print(f"step {step:5d} loss={float(m['loss']):.4f} {rate}"
                  f"sparse_words="
                  f"{float(m['sync/sparse_sent_words']):,.0f} "
                  f"overflow={int(float(m['sync/overflow']))}")
        if controller is not None and step % args.log_every == 0:
            controller.observe(
                {k: float(v) for k, v in m.items()
                 if k.startswith("sync/ef_density")})
        if (controller is not None and step
                and step % args.replan_every == 0):
            drift = controller.drifted()
            if drift:
                print(f"replan @ step {step}: density drift flips "
                      f"{drift} — rebuilding plan")
                attach_train(prog, args.seq_len, args.global_batch,
                             sparsity_profiles=controller.profiles())
                controller.rebase(prog.gradsync.bucket_schemes())
        if args.ckpt_dir and args.ckpt_every and \
                step and step % args.ckpt_every == 0:
            save(Path(args.ckpt_dir) / f"step_{step}",
                 {"params": params, "step": jnp.asarray(step)})
    if args.ckpt_dir:
        save(Path(args.ckpt_dir) / "final",
             {"params": params, "step": jnp.asarray(args.steps)})
    print("done")


if __name__ == "__main__":
    main()
