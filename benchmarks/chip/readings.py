"""The readings that the limits of ``correct`` are set from, many seeds in
one process (the benchmark's own runs never run this).

    python3 benchmarks/chip/readings.py --workload <cell> --seeds 12 \\
        --variant-seeds 3 --first-seed <n> [--out <file.jsonl>]

For every seed: the program's checked first steps, from the cell's
weights and batches, against the plain float32 reference (``check.py``):
the lower readings.  For the first ``--variant-seeds`` seeds also the
upper readings, each against the same float32 reference:

* ``control``: the reference computed with float8 (e4m3) matmul operands
  in the program's place, one precision step below the bfloat16 the
  configurations state;
* ``half``: the reference with half of each batch left out, the mean
  taken over the rest;
* ``no_exchange`` (cells on several chips): the reference trained on the
  first worker's rows alone, as if no gradient crossed chips.

A state left unchanged reads 1 on both leaf-wise numbers and needs no
run.  Each seed's numbers go to standard output as one JSON line, and to
``--out``.  Needs the chips the cell asks for; exits 2 without them.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--variant-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchmarks.chip import cells

    cell = cells.load_cell(args.workload)
    try:
        devices, _ = cells.require_chips(cell)
    except cells.NoChip as e:
        print(f"readings.py: {e}; nothing run", file=sys.stderr)
        return 2

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    enable_compile_cache()
    from benchmarks.chip import check, harness

    tr = cell.traffic
    prog = harness.Program.build(cell, devices)
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 1_000_003 * i
        t0 = time.perf_counter()
        s = harness.set_up(cell, prog, seed)
        readings, feed = s.readings, s.feed
        del s
        batches = [(b["tokens"], b["labels"]) for b in
                   (feed.host_batch(j) for j in range(tr["check_steps"]))]

        initial = check.flat(prog.weights_fn(cell)(check.seed_key(seed)))

        def ref(**kw):
            return check.reference_readings(
                cell.kind, cell.config, initial, batches, tr["optimizer"],
                dp=prog.dp, devices=devices, **kw)

        f32 = ref()
        line = {"seed": seed, "program": _numbers(check.compare(readings, f32)),
                "losses": readings.losses, "ref_losses": f32.losses,
                "leaves": {k: [readings.grad_norms[k], f32.grad_norms[k],
                               readings.change_norms[k], f32.change_norms[k]]
                           for k in f32.grad_norms}}
        if i < args.variant_seeds:
            variants = {"control": dict(precision="fp8"), "half": dict(fault="half")}
            if prog.dp > 1:
                variants["no_exchange"] = dict(fault="no_exchange")
            for name, kw in variants.items():
                r = ref(**kw)
                line[name] = _numbers(check.compare(r, f32))
                line[name + "_losses"] = r.losses
        del initial
        line["seconds"] = time.perf_counter() - t0
        text = json.dumps(line)
        print(text, flush=True)
        if out:
            out.write(text + "\n")
            out.flush()
    return 0


def _numbers(nums: dict) -> dict:
    return {k: [v, where] for k, (v, where) in nums.items()}


if __name__ == "__main__":
    sys.exit(main())
