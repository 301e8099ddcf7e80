"""Reduce a profiler trace of the measured window to device busy time,
idle share, device time per op and per program, and idle gaps attributed
to the harness's host spans.

The harness wraps each scheduler step, and the engine calls inside it, in
``jax.profiler.TraceAnnotation`` spans named ``bench.<what>``; the profiler
writes them on the host plane on the same clock as the device events.
The traced window runs from the start of the first ``bench.step`` span to
the end of the last.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os

STEP = "bench.step"
SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]        # device index -> ops
    modules: dict[int, list[Event]]    # device index -> program executions
    spans: list[Event]                 # harness host spans


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _labeller(spans: list[Event]):
    """``label(t)``: the name of the innermost harness span holding ``t``."""
    by_name: dict[str, list[tuple[float, float]]] = {}
    for sp in spans:
        by_name.setdefault(sp.name, []).append((sp.start_ns, sp.end_ns))
    for v in by_name.values():
        v.sort()
    starts = {k: [s for s, _ in v] for k, v in by_name.items()}

    def label(t: float) -> str:
        best, best_len = "outside spans", float("inf")
        for name, iv in by_name.items():
            i = bisect.bisect_right(starts[name], t) - 1
            # spans of one name do not nest, so only the latest start can hold t
            if i >= 0 and iv[i][1] >= t and iv[i][1] - iv[i][0] < best_len:
                best, best_len = name, iv[i][1] - iv[i][0]
        return best

    return label


def load(path: str) -> Trace:
    """Read an ``.xplane.pb``: device ops and modules per TPU, and the
    harness's host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: dict[int, list[Event]] = {}
    modules: dict[int, list[Event]] = {}
    spans: list[Event] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = int(plane.name[len(DEVICE_PLANE):].split()[0])
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                out = (ops if line.name == OPS_LINE else modules).setdefault(dev, [])
                for e in line.events:
                    # the TPU names an op by its whole HLO instruction text;
                    # keep the instruction's name, the part before " = "
                    out.append(Event(e.name.split(" = ", 1)[0], e.start_ns,
                                     e.end_ns))
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Event(e.name, e.start_ns, e.end_ns))
    return Trace(ops, modules, spans)


def merge(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Union of intervals, as sorted disjoint intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def self_times(events: list[Event]) -> list[float]:
    """Each event's duration less that of the events nested inside it (a
    ``while`` op holds the ops of its body on the same line), in the order
    given.  An event that only overlaps another is not nested in it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i].start_ns, -events[i].end_ns))
    own = [e.dur_ns for e in events]
    stack: list[int] = []
    for i in order:
        e = events[i]
        while stack and events[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack and events[stack[-1]].end_ns >= e.end_ns:
            own[stack[-1]] -= e.dur_ns
        stack.append(i)
    return own


def covered(merged: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Length of [lo, hi] that the merged intervals cover."""
    return sum(e - s for s, e in clip(merged, lo, hi))


@dataclasses.dataclass
class Reduced:
    window_ns: tuple[float, float]
    busy_ns: float                      # busy union, averaged over devices
    op_ns: dict[str, float]             # summed self time per op name
    module_ns: dict[str, list[float]]   # program name -> execution times
    step_spans: list[tuple[float, float]]
    step_busy_ns: list[float]           # device busy inside each step span
    gaps: list[tuple[float, str]]       # (idle ns, innermost host span)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def kernel_s(self, name: str) -> float:
        """Summed self time of the ops whose name holds ``name``."""
        return sum(t for op, t in self.op_ns.items() if name in op) / 1e9

    def program_s(self, name: str) -> list[float]:
        """Device time of each execution of the programs whose name holds
        ``name``, in seconds."""
        return [t / 1e9 for prog, ts in self.module_ns.items() if name in prog
                for t in ts]

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        by_span: dict[str, float] = {}
        for ns, label in self.gaps:
            by_span[label] = by_span.get(label, 0.0) + ns
        gaps = sorted(self.gaps, key=lambda g: -g[0])[:n]
        return {
            "device_ops": [[name, t / 1e9] for name, t in ops],
            "idle_gaps": [[label, ns / 1e9] for ns, label in gaps],
            "idle_by_span": [[k, v / 1e9] for k, v in
                             sorted(by_span.items(), key=lambda kv: -kv[1])[:n]],
        }


def reduce(trace: Trace) -> Reduced:
    """Reduce a trace to the traced window's numbers.  Device figures are
    averaged over the devices that ran ops."""
    steps = sorted((s.start_ns, s.end_ns) for s in trace.spans if s.name == STEP)
    if not steps:
        raise ValueError(f"no {STEP!r} host spans in the trace")
    lo, hi = steps[0][0], steps[-1][1]
    devs = sorted(d for d, evs in trace.ops.items() if evs)
    if not devs:
        raise ValueError("no device ops in the trace")
    busy = 0.0
    step_busy = [0.0] * len(steps)
    op_ns: dict[str, float] = {}
    merged0: list[tuple[float, float]] = []
    for i, d in enumerate(devs):
        evs = [e for e in trace.ops[d] if e.end_ns > lo and e.start_ns < hi]
        merged = merge(clip([(e.start_ns, e.end_ns) for e in evs], lo, hi))
        if i == 0:
            merged0 = merged
        busy += sum(e - s for s, e in merged) / len(devs)
        for j, (s, e) in enumerate(steps):
            step_busy[j] += covered(merged, s, e) / len(devs)
        for e, own in zip(evs, self_times(evs)):
            op_ns[e.name] = op_ns.get(e.name, 0.0) + own / len(devs)
    module_ns: dict[str, list[float]] = {}
    for d in devs:
        for e in trace.modules.get(d, []):
            if e.end_ns > lo and e.start_ns < hi:
                module_ns.setdefault(e.name, []).append(e.dur_ns)
    # idle gaps on the first device, each named by the innermost harness
    # span around its midpoint
    label = _labeller(trace.spans)
    gaps: list[tuple[float, str]] = []
    prev = lo
    for s, e in merged0 + [(hi, hi)]:
        if s > prev:
            gaps.append((s - prev, label((prev + s) / 2)))
        prev = max(prev, e)
    return Reduced((lo, hi), busy, op_ns, module_ns, steps,
                   step_busy, gaps)
