"""Record the scoped-step fixture on one TPU: the tiny qwen2 cell (2
layers, width 64), a traced tail of a few steps, and the program's map
from instruction to layer, as ``fixtures/scoped_tiny_v5e.json``.

    python3 benchmarks/chip/tests/chipbench_record_scopes.py <out.json>

Exits 2 without a TPU.  Times are rebased to the traced window's start
and rounded to the nanosecond, and the map keeps only the instructions
the trace names, so the fixture stays small."""
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[2]), str(HERE.parents[2] / "src")]

import jax  # noqa: E402

from benchmarks.chip import harness, tracing  # noqa: E402
from chipbench_tiny import tiny_cell  # noqa: E402

STEPS = 3


def main(out: Path) -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX found platform {dev.platform!r}", file=sys.stderr)
        return 2
    cell = tiny_cell("qwen2-0.5b.s512.uniform.1chip")
    prog = harness.Program.build(cell, [dev])
    s = harness.set_up(cell, prog, 2**31 + 7)
    harness._steps(s, s.next_step, lambda n: n < 8, False)
    log_dir = out.parent / "scopes_trace"
    jax.profiler.start_trace(str(log_dir))
    harness._steps(s, s.next_step + 8, lambda n: n < STEPS, True)
    jax.profiler.stop_trace()
    trace = tracing.extract(log_dir)
    lo = trace.window[0]

    def rebase(evs):
        return [(*head, round(a - lo), round(b - lo)) for *head, a, b in evs]

    trace = tracing.Trace(
        ops={d: rebase(v) for d, v in trace.ops.items()},
        async_ops={d: rebase(v) for d, v in trace.async_ops.items()},
        spans=rebase(trace.spans))
    mapping = prog.prog.step_scopes()
    named = {name for evs in (*trace.ops.values(), *trace.async_ops.values())
             for name, *_ in evs}
    out.write_text(json.dumps({
        "device_kind": dev.device_kind, "steps": STEPS,
        "trace": json.loads(trace.to_json()),
        "scopes": {k: v for k, v in mapping.items() if k in named}}))
    print(f"{out}: {out.stat().st_size} bytes, {len(named)} op names")
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1])))
