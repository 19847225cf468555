"""Run one cell of the chip benchmark once, on the chips of this machine.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout.  The cell is a ``workloads`` entry of
``BENCHMARK.json``; its configuration, traffic and limits are files under
this directory found by name (``cells.py``).  With ``--trace 0`` the last
line of standard output is one JSON object with the cell's end-to-end
metrics; with ``--trace 1`` the per-layer metrics, read from a profiled
tail after the measured window, and a ``breakdown``.  Both runs decide
``correct`` the same way (``check.py``) and print each compared number
beside its limit: last on standard error, and under ``checks``, the last
key of the result line.

Without a TPU, with fewer chips than the cell asks for, or on a
``device_kind`` that ``peaks.json`` does not know, it exits with code 2
and prints no result.  There is no fallback to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import cells

    cell = cells.load_cell(args.workload)
    try:
        devices, peak = cells.require_chips(cell)
    except cells.NoChip as e:
        log(f"run.py: {e}; nothing run")
        return 2

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    # every program of the run, however small, goes to the persistent cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"[cache] {enable_compile_cache()}")
    log(f"[device] {devices[0].device_kind} x{len(devices)}; cell {cell.name}")

    from benchmarks.chip import harness

    out_dir = HERE / "out" / cell.name
    res = harness.run_cell(cell, devices, peak, args.seed, args.seconds,
                           bool(args.trace), T_START, out_dir, log=log)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": res["memory_peak_bytes"]}
    if args.trace:
        device["busy_s"] = res["busy_s"]
        device["window_s"] = res["window_s"]
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": res["metrics"], "device": device}
    if args.trace:
        line["breakdown"] = res["breakdown"]
    line["checks"] = res["checks"]
    for k, v in res["checks"].items():
        log(f"[check] {k} {v['value']!r} limit {v['limit']!r} "
            f"(worst at {res['where'][k]})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
