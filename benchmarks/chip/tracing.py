"""From a profiler trace to device op intervals and host spans, and the
sums the per-layer metrics are made of.

``extract`` reads the ``.xplane.pb`` the JAX profiler writes, with
nothing but JAX: each ``/device:TPU:<n>`` plane's "XLA Ops" line (one
event per executed HLO instruction, named by its HLO text) and "Async XLA
Ops" line (the span of an asynchronous op from start to done), and the
host spans this benchmark writes with ``TraceAnnotation`` (``data``,
``dispatch``, ``wait``).  All times are nanoseconds on the trace's clock.
``Trace`` round-trips through JSON, which is what the test fixture holds.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import re
from pathlib import Path

HOST_SPANS = ("data", "dispatch", "wait")
COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


@dataclasses.dataclass
class Trace:
    # {device id: [(name, opcode, start_ns, end_ns)]}
    ops: dict
    async_ops: dict
    # [(span name, start_ns, end_ns)]
    spans: list

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self))

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        d = json.loads(text)

        def ev(m):
            return {int(k): [tuple(e) for e in v] for k, v in m.items()}

        return cls(ops=ev(d["ops"]), async_ops=ev(d["async_ops"]),
                   spans=[tuple(s) for s in d["spans"]])

    @property
    def window(self) -> tuple:
        """The traced window: from the first host span's start to the last
        host span's end."""
        return (min(s[1] for s in self.spans), max(s[2] for s in self.spans))


def op_name(text: str) -> tuple:
    """(instruction name, opcode) of an "XLA Ops" event's HLO text, e.g.
    ``%fusion.3 = bf16[8]{0} fusion(...)`` -> ("fusion.3", "fusion")."""
    head, _, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest)
    return head.lstrip("%").strip(), (m.group(1) if m else "")


def is_collective(opcode: str) -> bool:
    base = re.sub(r"-(start|done|update)$", "", opcode)
    return base in COLLECTIVES


def extract(log_dir: str | Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = ProfileData.from_file(files[-1])
    ops, async_ops, spans = {}, {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                target = {"XLA Ops": ops, "Async XLA Ops": async_ops}.get(line.name)
                if target is None:
                    continue
                evs = target.setdefault(dev, [])
                for e in line.events:
                    name, opcode = op_name(e.name)
                    evs.append((name, opcode, float(e.start_ns),
                                float(e.start_ns + e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append((e.name, float(e.start_ns),
                                      float(e.start_ns + e.duration_ns)))
    spans.sort(key=lambda s: s[1])
    return Trace(ops=ops, async_ops=async_ops, spans=spans)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals, lo: float, hi: float) -> list:
    """Merged, sorted intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list:
    """Merged intervals ``a`` minus merged intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def busy_ns(tr: Trace, dev: int) -> list:
    lo, hi = tr.window
    return union([(s, e) for _, _, s, e in tr.ops.get(dev, [])], lo, hi)


def busy_s(tr: Trace) -> float:
    """Seconds in which an op ran, averaged over the traced devices."""
    devs = sorted(tr.ops)
    return sum(length(busy_ns(tr, d)) for d in devs) / len(devs) / 1e9


def window_s(tr: Trace) -> float:
    lo, hi = tr.window
    return (hi - lo) / 1e9


def collective_ns(tr: Trace, dev: int) -> tuple:
    """(collective intervals, those not covered by any other op), merged,
    on one device within the traced window."""
    lo, hi = tr.window
    coll = [(s, e) for _, op, s, e in tr.ops.get(dev, []) + tr.async_ops.get(dev, [])
            if is_collective(op)]
    other = [(s, e) for _, op, s, e in tr.ops.get(dev, []) if not is_collective(op)]
    c = union(coll, lo, hi)
    return c, subtract(c, union(other, lo, hi))


def self_times(events) -> dict:
    """{op name: seconds} of self time (an event's duration minus the
    events nested inside it) summed over every execution."""
    out = {}
    evs = sorted(events, key=lambda e: (e[2], -e[3]))
    stack = []  # [name, start, end, child time]

    def close(item):
        name, s, e, child = item
        out[name] = out.get(name, 0.0) + (e - s - child) / 1e9
        if stack:
            stack[-1][3] += e - s

    for name, _, s, e in evs:
        while stack and stack[-1][2] <= s:
            close(stack.pop())
        stack.append([name, s, e, 0.0])
    while stack:
        close(stack.pop())
    return out


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most self time (summed over devices and
    divided by their number) and the longest idle gaps on the first
    device, each labelled by the host span that overlaps it most."""
    devs = sorted(tr.ops)
    lo, hi = tr.window
    totals = {}
    for d in devs:
        inside = [ev for ev in tr.ops[d] if ev[3] > lo and ev[2] < hi]
        for k, v in self_times(inside).items():
            totals[k] = totals.get(k, 0.0) + v / len(devs)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    gaps = subtract([[lo, hi]], busy_ns(tr, devs[0]))
    labelled = []
    for s, e in gaps:
        best, cover = "none", 0.0
        for name, hs, he in tr.spans:
            ov = min(e, he) - max(s, hs)
            if ov > cover:
                best, cover = name, ov
        labelled.append([best, (e - s) / 1e9])
    labelled.sort(key=lambda g: -g[1])
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": labelled[:top]}
