"""The program's own host spans in a profiler trace: where the device's idle
time in the traced window falls, phase by phase, and the host time of a
serving step.

``ContinuousScheduler.step`` wraps itself and each of its phases in a
``serve.*`` span (``repro.obs.tracing.span``), and every pass of Python's
garbage collector in ``serve.gc``; JAX marks each compile or persistent-
cache load with a ``backend_compile_and_load`` host event.  All of these
are on the profiler's clock, beside the device ops.  The window is the one
``trace_reduce.reduce`` takes: the first ``bench.step`` span's start to the
last one's end (or the ``serve.step`` spans', in a trace the harness did
not annotate).

    python3 bench/serve_spans.py <trace dir or .xplane.pb>

prints the window's idle time by innermost span, the idle gaps of 10 ms
or more with the spans that held them, each phase's host time a step, and
the two per-step figures below, as JSON.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import os
import sys

if __package__ in (None, ""):       # run as a script: the checkout's root
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import trace_reduce as tr

SERVE = "serve."
STEP = "serve.step"
TOKEN_SYNC = "serve.token_sync"
APPEND = "serve.append_capacity"
GC = "serve.gc"
COMPILE = "backend_compile_and_load"
LONG_GAP_NS = 10e6


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float
    stats: tuple = ()              # (key, value) pairs of the event

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns

    def stat(self, key: str, default=None):
        return dict(self.stats).get(key, default)


def _kept(name: str) -> bool:
    return (name.startswith(SERVE) or name.startswith(tr.SPAN_PREFIX)
            or name == COMPILE)


def load_spans(path: str) -> list[Span]:
    """The host spans of an ``.xplane.pb``: ``serve.*``, ``bench.*`` and
    JAX's compile events, with their stats."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(tr.DEVICE_PLANE):
            continue
        for line in plane.lines:
            for e in line.events:
                if _kept(e.name):
                    stats = tuple(e.stats) if e.name.startswith(SERVE) else ()
                    out.append(Span(e.name, e.start_ns, e.end_ns, stats))
    return out


def window(spans: list[Span]) -> tuple[float, float] | None:
    """The traced window: from the first step span's start to the last
    one's end, ``bench.step`` where there is one, else ``serve.step``."""
    for name in (tr.STEP, STEP):
        steps = [s for s in spans if s.name == name]
        if steps:
            return (min(s.start_ns for s in steps), max(s.end_ns for s in steps))
    return None


def _inside(spans: list[Span], name: str, lo: float, hi: float) -> list[Span]:
    return [s for s in spans
            if s.name == name and s.start_ns >= lo and s.end_ns <= hi]


def host_serial_ms_per_step(spans: list[Span], win=None) -> float | None:
    """Mean over the window's ``serve.step`` spans of each one's length
    less the ``serve.token_sync`` inside it, in ms: the host time a step
    spends off the device's critical path."""
    lo, hi = win or window(spans) or (0.0, 0.0)
    steps = _inside(spans, STEP, lo, hi)
    if not steps:
        return None
    syncs = _inside(spans, TOKEN_SYNC, lo, hi)
    host = [s.dur_ns - sum(y.dur_ns for y in syncs
                           if y.start_ns >= s.start_ns and y.end_ns <= s.end_ns)
            for s in steps]
    return sum(host) / len(host) / 1e6


def append_capacity_ms_per_step(spans: list[Span], win=None) -> float | None:
    """Summed ``serve.append_capacity`` time in the window over the
    window's ``serve.step`` spans, in ms."""
    lo, hi = win or window(spans) or (0.0, 0.0)
    steps = _inside(spans, STEP, lo, hi)
    appends = _inside(spans, APPEND, lo, hi)
    if not steps or not appends:
        return None
    return sum(s.dur_ns for s in appends) / len(steps) / 1e6


def phase_ms_per_step(spans: list[Span], win=None) -> dict[str, float]:
    """Each ``serve.*`` span name's summed time in the window over the
    window's ``serve.step`` spans, in ms."""
    lo, hi = win or window(spans) or (0.0, 0.0)
    n = len(_inside(spans, STEP, lo, hi))
    out: dict[str, float] = {}
    for s in spans:
        if s.name.startswith(SERVE) and s.start_ns >= lo and s.end_ns <= hi:
            out[s.name] = out.get(s.name, 0.0) + s.dur_ns
    return {k: v / max(n, 1) / 1e6 for k, v in sorted(out.items())}


def _overlapping(spans: list[Span]):
    """``over(a, b)``: the spans that overlap (a, b)."""
    order = sorted(spans, key=lambda s: s.start_ns)
    starts = [s.start_ns for s in order]
    longest = max((s.dur_ns for s in order), default=0.0)

    def over(a: float, b: float) -> list[Span]:
        i = bisect.bisect_left(starts, a - longest)
        j = bisect.bisect_left(starts, b)
        return [s for s in order[i:j] if s.end_ns > a]

    return over


def explained(name: str) -> bool:
    """A span that names a phase of the step: a ``serve.*`` span below
    ``serve.step`` (``serve.gc`` among them) or a compile."""
    return (name.startswith(SERVE) and name != STEP) or name == COMPILE


def _split(over: list[Span], a: float, b: float) -> tuple[dict[str, float], float]:
    """[a, b] cut at every boundary of the spans ``over`` it: each piece's
    length by the innermost (shortest) span that holds it, and the length
    of the pieces that some :func:`explained` span holds."""
    cuts = sorted({a, b} | {t for s in over for t in (s.start_ns, s.end_ns)
                            if a < t < b})
    out: dict[str, float] = {}
    named = 0.0
    for x, y in zip(cuts, cuts[1:]):
        mid = (x + y) / 2
        holders = [s for s in over if s.start_ns <= mid <= s.end_ns]
        name = (min(holders, key=lambda s: s.dur_ns).name if holders
                else "outside spans")
        out[name] = out.get(name, 0.0) + (y - x)
        if any(explained(s.name) for s in holders):
            named += y - x
    return out, named


def idle(trace: tr.Trace, spans: list[Span], win=None) -> dict:
    """The device's idle time in the window (first device that ran ops),
    by innermost span of either family; the gaps of 10 ms or more, each
    with the spans that held it and the ``serve.step`` stats around it;
    and the share of idle time inside a span that :func:`explained`
    accepts (a ``bench.*`` span nested in one counts as inside it)."""
    lo, hi = win or window(spans)
    devs = sorted(d for d, evs in trace.ops.items() if evs)
    merged = tr.merge(tr.clip([(e.start_ns, e.end_ns) for e in trace.ops[devs[0]]],
                              lo, hi)) if devs else []
    by_span: dict[str, float] = {}
    named = 0.0
    long_gaps = []
    prev = lo
    steps = _inside(spans, STEP, lo, hi)
    over = _overlapping(spans)
    for s, e in merged + [(hi, hi)]:
        if s > prev:
            parts, n = _split(over(prev, s), prev, s)
            named += n
            for k, v in parts.items():
                by_span[k] = by_span.get(k, 0.0) + v
            if s - prev >= LONG_GAP_NS:
                step = next((x for x in steps
                             if x.start_ns <= prev and s <= x.end_ns), None)
                long_gaps.append({
                    "s": (s - prev) / 1e9,
                    "spans": {k: v / 1e9 for k, v in
                              sorted(parts.items(), key=lambda kv: -kv[1])},
                    "step": dict(step.stats) if step else None})
        prev = max(prev, e)
    total = sum(by_span.values())
    return {
        "window_s": (hi - lo) / 1e9,
        "idle_s": total / 1e9,
        "explained_share": named / total if total else None,
        "idle_by_span": {k: v / 1e9 for k, v in
                         sorted(by_span.items(), key=lambda kv: -kv[1])},
        "long_gaps": sorted(long_gaps, key=lambda g: -g["s"]),
    }


def report(path: str) -> dict:
    """Everything above for one ``.xplane.pb`` (or the newest under a
    profiler log directory)."""
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    trace, spans = tr.load(path), load_spans(path)
    win = window(spans)
    steps = _inside(spans, STEP, *win)
    return {
        "steps": len(steps),
        "new_programs": sum(s.stat("new_programs", 0) for s in steps),
        "host_serial_ms_per_step": host_serial_ms_per_step(spans, win),
        "append_capacity_ms_per_step": append_capacity_ms_per_step(spans, win),
        "phase_ms_per_step": phase_ms_per_step(spans, win),
        "gc_passes": len(_inside(spans, GC, *win)),
        "compiles": len(_inside(spans, COMPILE, *win)),
        **idle(trace, spans, win),
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
