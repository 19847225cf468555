"""One run of one cell through the trainer's normal path.

``build_program`` -> ``attach_train`` -> ``prog.train_step``, the calls
``launch/train.py`` makes, with weights this benchmark makes from the
seed (one jitted call, in the dtypes the program stores) and the
program's own zero optimizer state.  Set-up drives that one compiled step
through the first ``check_steps`` steps of the feed and keeps what they
read; the measured window continues the same object from there.  After
the window, with the program's state freed, the plain reference replays
those first steps and ``check`` decides ``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import math
import shutil
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmarks.chip import check, tracing
from benchmarks.chip.cells import Cell
from benchmarks.chip.feed import ZipfFeed

TRACE_TAIL_S = 3.0


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric's reducer reads."""
    cell: Cell
    chips: int
    peak: dict
    flops_per_token: float
    tokens_per_step: int
    step_s: list
    tokens_per_s: float
    host_data_s: list
    counters: list
    trace: tracing.Trace | None = None
    traced_steps: int = 0


def arch_config(cell: Cell):
    """The program's ArchConfig for the cell: its registry entry with the
    configuration file's sizes applied (a no-op where they agree)."""
    from repro.configs import get_config

    ref = check.reference_module(cell.kind)
    fields = {f: cell.config[k] for k, f in ref.ARCH_FIELDS.items()
              if k in cell.config}
    return dataclasses.replace(get_config(cell.config["arch"]), **fields)


@dataclasses.dataclass
class Program:
    """The program under test with its mesh, shardings and the cell's
    weights function."""
    prog: object
    shapes: dict          # {leaf path: (shape, dtype)} as the program stores them
    treedef: object
    param_shardings: object
    batch_shardings: dict
    dp: int
    _weights: object = None

    @classmethod
    def build(cls, cell: Cell, devices) -> "Program":
        from repro.core.zen import SyncConfig
        from repro.optim.optimizers import OptConfig
        from repro.train.build import attach_train, build_program
        from repro.train.steps import TrainerConfig

        tr = cell.traffic
        mesh = Mesh(np.array(devices).reshape(tr["mesh"]), ("data", "model"))
        tcfg = TrainerConfig(opt=OptConfig(**tr["optimizer"]),
                             sync=SyncConfig(scheme=tr["sync"]))
        prog = build_program(arch_config(cell), mesh, tcfg)
        attach_train(prog, tr["seq_len"], tr["global_batch"])
        flat, treedef = jax.tree_util.tree_flatten(prog.param_shapes)
        paths = check.leaf_paths(prog.param_shapes)
        shapes = {p: (s.shape, s.dtype) for p, s in zip(paths, flat)}
        is_p = lambda x: isinstance(x, P)  # noqa: E731
        pshard = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              prog.param_specs, is_leaf=is_p)
        bshard = {k: NamedSharding(mesh, s)
                  for k, s in prog.batch_specs["pspecs"].items()}
        return cls(prog=prog, shapes=shapes, treedef=treedef,
                   param_shardings=pshard, batch_shardings=bshard,
                   dp=tr["mesh"][0])

    def weights_fn(self, cell: Cell):
        """The one jitted call that makes the weights from a key, in the
        dtypes and shardings the program keeps them in.  Kept, so that
        calling it again gives the very same weights for the reference."""
        if self._weights is None:
            kind, c, shapes, treedef = cell.kind, cell.config, self.shapes, self.treedef

            def make(key):
                vals = check.init_values(kind, c, shapes, key)
                return jax.tree_util.tree_unflatten(treedef, [vals[p] for p in shapes])

            self._weights = jax.jit(make, out_shardings=self.param_shardings)
        return self._weights

    def put(self, host: dict) -> dict:
        return {k: jax.device_put(v, self.batch_shardings[k])
                for k, v in host.items()}


def _grad_norms_from_moment(b1: float):
    """Per-leaf norms of the gradient AdamW took in its first step,
    recovered from the first moment: m1 = (1 - b1) g1."""
    def fn(opt):
        leaves = opt["leaves"]
        flat = jax.tree_util.tree_flatten_with_path(
            leaves, is_leaf=lambda x: isinstance(x, dict) and "m" in x)[0]
        return {"/".join(str(getattr(k, "key", k)) for k in kp):
                jnp.sqrt(jnp.sum(jnp.square(st["m"]))) / (1.0 - b1)
                for kp, st in flat}
    return jax.jit(fn)


@dataclasses.dataclass
class Setup:
    prog: Program
    step: object
    feed: ZipfFeed
    params: object
    opt: object
    readings: check.Readings
    next_step: int


def set_up(cell: Cell, prog: Program, seed: int, *, step_fault=None) -> Setup:
    """Make the weights and drive the compiled step through the checked
    first steps.  ``step_fault`` (tests only) wraps the step."""
    tr = cell.traffic
    feed = ZipfFeed(cell.config["vocab_size"], tr["seq_len"],
                    tr["global_batch"], tr["zipf"], seed)
    key = check.seed_key(seed)
    params = prog.weights_fn(cell)(key)
    opt = prog.prog.init_opt(params)
    step = prog.prog.train_step
    if step_fault is not None:
        step = step_fault(step, prog)
    b1 = tr["optimizer"]["b1"]
    losses, gnorms = [], None
    for i in range(tr["check_steps"]):
        params, opt, m = step(params, opt, prog.put(feed.host_batch(i)))
        losses.append(m["loss"])
        if i == 0:
            gnorms = _grad_norms_from_moment(b1)(opt)
    # the starting point made again by the same compiled call: a second
    # compile of the same draw, fused with the norm, can round differently
    change = check.change_norms(check.flat(params),
                                check.flat(prog.weights_fn(cell)(key)))
    readings = check.Readings(
        losses=[float(x) for x in losses],
        grad_norms={k: float(v) for k, v in gnorms.items()},
        change_norms={k: float(v) for k, v in change.items()})
    return Setup(prog=prog, step=step, feed=feed, params=params, opt=opt,
                 readings=readings, next_step=tr["check_steps"])


def _steps(s: Setup, first: int, until, annotate: bool):
    """Run steps from feed index ``first`` while ``until(n_done)`` holds;
    each step is timed by its completion while the next one is already
    enqueued.  Returns (completion times, per-step metrics, data spans)."""
    step = s.step
    done, metrics, data_s = [], [], []
    pending, i = None, first

    def span(name):
        return (jax.profiler.TraceAnnotation(name) if annotate
                else contextlib.nullcontext())

    while until(len(metrics)):
        t0 = time.perf_counter()
        with span("data"):
            batch = s.prog.put(s.feed.host_batch(i))
        t1 = time.perf_counter()
        with span("dispatch"):
            s.params, s.opt, m = step(s.params, s.opt, batch)
        if pending is not None:
            with span("wait"):
                pending.block_until_ready()
            done.append(time.perf_counter())
        data_s.append(t1 - t0)
        pending = m["loss"]
        metrics.append(m)
        i += 1
    with span("wait"):
        pending.block_until_ready()
    done.append(time.perf_counter())
    return done, metrics, data_s


def run_cell(cell: Cell, devices, peak: dict, seed: int, seconds: float,
             trace: bool, t_start: float, out_dir: Path, *, step_fault=None,
             log=print) -> dict:
    """One whole run: set-up, window, optional traced tail, reference.
    Returns the result line's dict (without ``device``)."""
    s = set_up(cell, Program.build(cell, devices), seed, step_fault=step_fault)
    tr = cell.traffic
    tokens_per_step = tr["seq_len"] * tr["global_batch"]
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    log(f"[setup] {setup_s:.3f} s; checked-step losses {s.readings.losses}")

    done, metrics, data_s = _steps(
        s, s.next_step, lambda n: time.perf_counter() - t0 < seconds, False)
    window = done[-1] - t0
    step_s = [b - a for a, b in zip([t0] + done[:-1], done)]
    n_steps = len(done)
    tokens_per_s = n_steps * tokens_per_step / window
    step_p90 = float(np.percentile(step_s, 90))
    slow = sorted(range(n_steps), key=lambda i: -step_s[i])[:3]
    log(f"[window] {n_steps} steps in {window:.3f} s; median step "
        f"{1e3 * statistics.median(step_s):.2f} ms, p90 {1e3 * step_p90:.2f} ms; "
        f"slowest {[(i, round(1e3 * step_s[i], 2)) for i in slow]}")

    trace_rec, traced = None, 0
    if trace:
        k = max(3, math.ceil(TRACE_TAIL_S / statistics.median(step_s)))
        log_dir = out_dir / "trace"
        shutil.rmtree(log_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)
        _steps(s, s.next_step + n_steps, lambda n: n < k, True)
        jax.profiler.stop_trace()
        trace_rec, traced = tracing.extract(log_dir), k
        if not any(trace_rec.ops.values()):
            raise RuntimeError("the traced window holds no device op")
        (out_dir / "trace.json").write_text(trace_rec.to_json())

    counters = [{k: float(v) for k, v in m.items()}
                for m in jax.device_get(metrics)]
    failed = sum(not math.isfinite(c["loss"]) for c in counters)
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devices)
    readings, feed, prog = s.readings, s.feed, s.prog
    del s, metrics          # the program's state is freed before the reference

    flops_mod = importlib.import_module(f"benchmarks.chip.flops.{cell.kind}")
    rec = RunRecord(cell=cell, chips=len(devices), peak=peak,
                    flops_per_token=flops_mod.flops_per_token(cell.config, tr["seq_len"]),
                    tokens_per_step=tokens_per_step, step_s=step_s,
                    tokens_per_s=tokens_per_s, host_data_s=data_s,
                    counters=counters, trace=trace_rec, traced_steps=traced)
    out = {"correct": False, "attempted": n_steps, "failed": failed}
    if trace:
        values = {}
        for m in cell.per_layer:
            v = cell.module("metrics", m["name"]).reduce(rec)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = values
        out["busy_s"] = tracing.busy_s(trace_rec)
        out["window_s"] = tracing.window_s(trace_rec)
        out["breakdown"] = tracing.breakdown(trace_rec)
    else:
        e2e = {"tokens_per_s": tokens_per_s, "step_ms_p90": 1e3 * step_p90,
               "setup_s": setup_s}
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                          for m in cell.end_to_end}
    out["memory_peak_bytes"] = int(mem)

    t_ref = time.perf_counter()
    batches = [feed.host_batch(i) for i in range(tr["check_steps"])]
    initial = check.flat(prog.weights_fn(cell)(check.seed_key(seed)))
    ref = check.reference_readings(
        cell.kind, cell.config, initial,
        [(b["tokens"], b["labels"]) for b in batches],
        tr["optimizer"], dp=tr["mesh"][0], devices=devices)
    del initial
    numbers = check.compare(readings, ref)
    ok, checks = check.judge(numbers, cell.limits)
    log(f"[reference] {time.perf_counter() - t_ref:.3f} s; losses {ref.losses}")
    out["correct"] = bool(ok and failed == 0)
    out["checks"] = checks
    out["where"] = {k: v[1] for k, v in numbers.items()}
    return out
