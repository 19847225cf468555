"""The tiny qwen2 step at dp=4 on 4 virtual CPU devices: its map from
instruction to layer, and every collective of the compiled step with its
layer; prints one JSON object.  A child process of the tests, since the
device count is fixed when JAX starts."""
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[2]), str(HERE.parents[2] / "src")]

import collections  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402

from benchmarks.chip import harness  # noqa: E402
from chipbench_tiny import tiny_cell  # noqa: E402
from repro.analysis import scopes  # noqa: E402
from repro.analysis.hlo_ir import HloModule  # noqa: E402


def main():
    # a vocabulary of 4,096 keeps a worker's uniform ids inside zen's row
    # budget, as at full size (chipbench_dp4_worker.py)
    cell = tiny_cell(config="qwen2-0.5b", traffic_name="s512.b32.dp4.uniform")
    cell = dataclasses.replace(cell, config=dict(cell.config, vocab_size=4096))
    prog = harness.Program.build(cell, jax.devices()[:4])
    s = harness.set_up(cell, prog, 2**31 + 11)
    batch = prog.put(s.feed.host_batch(s.next_step))
    text = prog.prog.train_step.lower(s.params, s.opt, batch).compile().as_text()
    mapping = prog.prog.step_scopes()
    collectives = [
        {"kind": op.kind, "layer": mapping[op.name],
         "scalars": all(leaf.elems == 1 for leaf in op.leaves)}
        for _, op in HloModule.parse(text).all_ops()
        if op.collective and op.collective[1] != "done"]
    print(json.dumps({
        "same_map": mapping == scopes.instruction_scopes(text),
        "layers": collections.Counter(mapping.values()),
        "collectives": collectives}))


if __name__ == "__main__":
    main()
