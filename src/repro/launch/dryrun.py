import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  This process exists only for lower()+compile()
# against the production meshes — nothing here allocates real arrays.

import argparse      # noqa: E402
import gc            # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402
from pathlib import Path  # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np   # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import ALL_ARCHS, INPUT_SHAPES, get_config  # noqa: E402
from repro.core.registry import cli_scheme_choices  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.train import steps as st  # noqa: E402
from repro.train.build import (  # noqa: E402
    attach_serve, attach_train, build_program,
)
from repro.train.steps import TrainerConfig  # noqa: E402

def dryrun_combo(arch: str, shape: str, multi_pod: bool,
                 sync_scheme: str = "zen", pad_heads: bool = False,
                 fused_attn: bool = False, moe_a2a: bool = False,
                 bucket_bytes: int | None = None,
                 compress: str = "none", node_size: int = 1,
                 alpha_beta: str | None = None,
                 calib_file: str | None = None) -> dict:
    """Lower + compile one (arch, input-shape, mesh) combination.

    Returns the record for EXPERIMENTS.md §Dry-run / §Roofline.
    ``pad_heads`` / ``fused_attn`` are the §Perf optimization knobs;
    ``bucket_bytes`` compiles the bucketed overlap schedule (DESIGN.md §7)
    so its collective count/bytes land in the record; ``compress``
    compiles the EF sparsification stack (DESIGN.md §8, e.g. 'topk:0.01')
    so induced-sparsity wire volumes are measurable on the production
    mesh; ``node_size`` compiles the hierarchical two-level sync
    (DESIGN.md §10 — the data axis splits into (dp_inter, dp_intra) and
    every bucket runs its CommPlan, so per-level collective bytes land in
    the record); ``calib_file`` plans from a measured-time calibration
    table (DESIGN.md §11 — must already exist; produce it with
    ``python -m repro.core.costmodel``) so the compiled plan matches what
    a calibrated trainer would run.
    """
    from repro.core.zen import SyncConfig

    cfg = get_config(arch)
    spec = INPUT_SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod, node_size=node_size)
    t0 = time.time()
    prog = build_program(cfg, mesh, TrainerConfig(
        sync=SyncConfig(scheme=sync_scheme, bucket_bytes=bucket_bytes,
                        compress=compress, alpha_beta=alpha_beta,
                        calib_file=calib_file)),
        pad_heads=pad_heads, moe_a2a=moe_a2a)
    mode = spec["mode"]

    if mode == "train":
        attach_train(prog, spec["seq_len"], spec["global_batch"])
        for line in prog.gradsync.describe():
            print(f"  {line}", flush=True)
        ospecs_abs = st.abstract_opt_state(prog.tcfg, prog.param_shapes,
                                           prog.model.ctx, prog.param_specs,
                                           gradsync=prog.gradsync)
        args = (prog.param_shapes, ospecs_abs, prog.batch_specs["shapes"])
        step = prog.train_step
    elif mode == "prefill":
        attach_serve(prog, spec["seq_len"], spec["global_batch"], "prefill")
        args = (prog.param_shapes, prog.batch_specs["shapes"])
        step = prog.prefill_step
    else:  # decode
        attach_serve(prog, spec["seq_len"], spec["global_batch"], "decode")
        B = spec["global_batch"]
        tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
        args = (prog.param_shapes, prog.cache_specs["global_shapes"], tok)
        step = prog.decode_step

    lowered = step.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    from repro.launch import hlo_cost
    walked = hlo_cost.analyze(
        hlo, exclude_bytes_re="flash_fusable" if fused_attn else None)

    rec = {
        "arch": arch, "shape": shape,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        # trip-count-aware walker numbers (cost_analysis counts each scan
        # body once — see hlo_cost docstring); xla_* kept for reference
        "flops_per_device": float(walked["flops"]),
        "bytes_per_device": float(walked["bytes"]),
        "xla_flops_per_device": float(cost.get("flops", -1.0)),
        "xla_bytes_per_device": float(cost.get("bytes accessed", -1.0)),
        "collectives": walked["collectives"],
        "collective_bytes_total": int(walked["collective_bytes_total"]),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", -1),
            "output_bytes": getattr(mem, "output_size_in_bytes", -1),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", -1),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", -1),
        },
        "n_params": cfg.n_params(),
        "n_active_params": cfg.n_active_params(),
        # tokens processed by one step of this program
        "tokens_per_step": spec["global_batch"] * (
            1 if mode == "decode" else spec["seq_len"]),
    }
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None, choices=ALL_ARCHS + [None])
    ap.add_argument("--shape", default=None,
                    choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="all (arch x shape) combos")
    ap.add_argument("--sync", default="zen",
                    choices=cli_scheme_choices())
    ap.add_argument("--bucket-bytes", type=int, default=None,
                    help="fuse dense grads into buckets of at most this "
                         "many bytes and emit the double-buffered overlap "
                         "schedule (DESIGN.md §7); default: monolithic")
    ap.add_argument("--compress", default="none",
                    help="EF-sparsify dense buckets before sync "
                         "(DESIGN.md §8), e.g. 'topk:0.01', 'randk:0.05', "
                         "'threshold:1e-3', ':noef' suffix disables error "
                         "feedback; default: none")
    ap.add_argument("--node-size", type=int, default=1,
                    help="devices per node (DESIGN.md §10): compile the "
                         "hierarchical two-level sync — the data axis "
                         "splits into (dp_inter, dp_intra) and each "
                         "bucket's CommPlan aggregates intra-node before "
                         "crossing the inter-node links")
    ap.add_argument("--alpha-beta", default=None,
                    help="α-β link override for the topology cost model "
                         "('a_intra,b_intra,a_inter,b_inter' in µs, "
                         "µs/word)")
    ap.add_argument("--calib-file", default=None,
                    help="measured-time calibration table (DESIGN.md §11) "
                         "for encode-cost-aware plan choice; must exist "
                         "(write one with `python -m repro.core.costmodel"
                         " --calib-file PATH`)")
    ap.add_argument("--pad-heads", action="store_true",
                    help="§Perf: pad+shard replicated attention heads")
    ap.add_argument("--fused-attn", action="store_true",
                    help="§Perf: account flash-attention internals as fused"
                         " (VMEM-resident, validated by the Pallas kernel)")
    ap.add_argument("--moe-a2a", action="store_true",
                    help="§Perf: token-sharded MoE all-to-all dispatch")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    archs = ALL_ARCHS if args.all or not args.arch else [args.arch]
    shapes = list(INPUT_SHAPES) if args.all or not args.shape \
        else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) \
        else [args.multi_pod]

    n_ok = n_fail = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}"
                fp = outdir / f"{tag}.json"
                if args.skip_existing and fp.exists():
                    prev = json.loads(fp.read_text())
                    if "error" not in prev:
                        continue
                try:
                    rec = dryrun_combo(arch, shape, mp, args.sync,
                                       pad_heads=args.pad_heads,
                                       fused_attn=args.fused_attn,
                                       moe_a2a=args.moe_a2a,
                                       bucket_bytes=args.bucket_bytes,
                                       compress=args.compress,
                                       node_size=args.node_size,
                                       alpha_beta=args.alpha_beta,
                                       calib_file=args.calib_file)
                    fp.write_text(json.dumps(rec, indent=1))
                    print(f"OK   {tag}: compile={rec['compile_s']}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"coll={rec['collective_bytes_total']:.3e}B",
                          flush=True)
                    n_ok += 1
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc(limit=4)
                    fp.write_text(json.dumps(
                        {"arch": arch, "shape": shape, "mesh": mp,
                         "error": f"{type(e).__name__}: {e}"}))
                    print(f"FAIL {tag}: {type(e).__name__}: {str(e)[:200]}",
                          flush=True)
                    n_fail += 1
                jax.clear_caches()
                gc.collect()
    print(f"dry-run complete: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
