"""Run the dp=4 cell at a tiny size on 4 virtual CPU devices, sound and
with each planted fault; prints one JSON object {case: correct}.  A child
process of the tests, since the device count is fixed when JAX starts."""
import os
import sys
import time
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[2]), str(HERE.parents[2] / "src")]

import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402

from benchmarks.chip import cells, harness  # noqa: E402
import chipbench_faults  # noqa: E402
from chipbench_tiny import tiny_cell  # noqa: E402


def main():
    # the dp=4 traffic with the one-chip cell's limits (the same
    # configuration; no dp=4 cell in BENCHMARK.json yet), and a vocabulary
    # of 4,096 so that a worker's 256 uniform ids stay inside zen's 25 %
    # row budget, as 4,096 ids in 151,936 rows do at full size
    cell = tiny_cell(config="qwen2-0.5b", traffic_name="s512.b32.dp4.uniform")
    cell = dataclasses.replace(
        cell, config=dict(cell.config, vocab_size=4096),
        limits=cells.load_cell("qwen2-0.5b.s512.uniform.1chip").limits)
    devices = jax.devices()[:4]
    assert len({d.id for d in devices}) == 4
    peak = next(iter(cells.peaks().values()))
    out = {}
    for case in ["sound", *chipbench_faults.MULTI_CHIP]:
        res = harness.run_cell(
            cell, devices, peak, 2**31 + 99, 0.5, False, time.perf_counter(),
            Path(sys.argv[1]), step_fault=chipbench_faults.MULTI_CHIP.get(case),
            log=lambda m: None)
        out[case] = {"correct": res["correct"], "checks": res["checks"]}
    print(json.dumps(out))


if __name__ == "__main__":
    main()
