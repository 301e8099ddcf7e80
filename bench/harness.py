"""One run of one benchmark cell: build the served FIER path, bring the
cell's sessions resident, time a window of ``ContinuousScheduler.step()``
calls, reduce the trace when asked, and decide ``correct`` against the
plain reference.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is found by name (see ``load_cell``):

* ``bench/configs/<config>.json``: the model's sizes as run, its source
  and the reference module that computes it;
* ``bench/arch/<config>.py`` and ``bench/reference/<reference>.py``: the
  configuration's architecture module and plain reference (``Block``);
* ``bench/mixes/<traffic>.json``: the traffic ``kind``, its parameters,
  and the serving deployment (slots, capacity, FIER budget, chunk);
* ``bench/traffic/<kind>.py``: the generator of that kind;
* ``bench/cells/<workload>.json``: the limits of the comparison;
* ``bench/metrics/<metric>.py``: the reader of a per-layer metric.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
WARM_STEPS = 4           # pure-decode steps after the last admission
TRACE_DIR = ".bench_trace"


def log(*a) -> None:
    print(*a, flush=True)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    root: Path = ROOT

    @property
    def deployment(self) -> dict:
        return self.mix["deployment"]

    @functools.cached_property
    def block(self) -> "Block":
        return block(self.config, self.root)


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """Resolve a workload of BENCHMARK.json by name to its files."""
    spec = load_spec(root)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = next(c for c in spec["configs"] if c["name"] == w["config"])
    applies = lambda m: name in m.get("workloads", [name])
    return Cell(
        name=name, chips=w["chips"], config=_json(root / conf["file"]),
        mix=_json(root / "bench" / "mixes" / f"{w['traffic']}.json"),
        limits=_json(root / "bench" / "cells" / f"{name}.json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)],
        root=root,
    )


def traffic_module(kind: str):
    return importlib.import_module(f"bench.traffic.{kind}")


def metric_module(name: str):
    return importlib.import_module(f"bench.metrics.{name}")


# ---------------------------------------------------- a configuration's block

@dataclasses.dataclass(frozen=True)
class Block:
    """Everything of a configuration that follows its architecture, found
    by name, so that a configuration with another block comes as new files.

    ``arch`` is ``bench/arch/<config name>.py``.  It provides
      ``model_config(config)``: the program's ModelConfig at the file's
        sizes, raising where the program's block is not the file's;
      ``layer_view(params, layer)`` and ``head_view(params, vocab)``: one
        layer's weights, and the embedding and LM head, under the names
        the reference reads them by;
      ``weight_work(config, batch)``: (FLOPs, bytes) of the weights in one
        decode step of ``batch`` tokens (``bench/work.py`` counts the rest).
    ``reference`` is ``bench/reference/<the file's "reference">.py``.  It
    imports nothing of the program, and provides
      ``BLOCK``: the file's statement of the block it computes;
      ``logits(config, dep, layer_weights, head, tokens, first_row, *,
        lowp)``: the float32 logits of the rows from ``first_row`` on
        (``bench/reference/fier.py``), or the float8 control's.
    """
    arch: ModuleType
    reference: ModuleType

    ARCH = ("model_config", "layer_view", "head_view", "weight_work")
    REFERENCE = ("BLOCK", "logits")


def _module(path: Path) -> ModuleType:
    """The module in the file ``path``, imported once a process."""
    name = f"bench_file:{path.resolve()}"
    if name not in sys.modules:
        if not path.is_file():
            raise FileNotFoundError(f"no module {path}")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def block(config: dict, root: Path = ROOT) -> Block:
    """A configuration's architecture module and reference, checked to
    provide what ``Block`` names and to compute the block the file states."""
    b = Block(_module(root / "bench" / "arch" / f"{config['name']}.py"),
              _module(root / "bench" / "reference" / f"{config['reference']}.py"))
    for mod, names in ((b.arch, Block.ARCH), (b.reference, Block.REFERENCE)):
        missing = [n for n in names if not hasattr(mod, n)]
        if missing:
            raise AttributeError(f"{mod.__file__} lacks {missing}")
    stated = {k: config.get(k) for k in b.reference.BLOCK}
    if stated != b.reference.BLOCK:
        raise ValueError(f"{config['name']}: the file states the block {stated}, its "
                         f"reference {config['reference']!r} computes {b.reference.BLOCK}")
    return b


def model_config(config: dict, root: Path = ROOT):
    """The program's ModelConfig for a configuration file, from its
    architecture module."""
    return block(config, root).arch.model_config(config)


# --------------------------------------------------------------- the program

def build_engine(cell: Cell):
    from repro.serving import Engine
    from repro.serving.engine import serving_policy

    dep = cell.deployment
    pol = dataclasses.replace(
        serving_policy(budget=dep["budget"], group=dep["group"],
                       skip_layers=dep["skip_layers"], sink=dep["sink"],
                       recent=dep["recent"], pipeline="one_pass", layout="paged"),
        block_size=dep["block_size"],
    )
    return Engine.build(cell.block.arch.model_config(cell.config), n_slots=dep["slots"],
                        capacity=dep["capacity"], policy=pol, layout="paged",
                        block_size=dep["block_size"])


def make_params(eng, seed: int):
    import jax

    from bench import weights

    shapes = jax.eval_shape(eng.bundle.init, jax.random.PRNGKey(0))
    params = weights.make(shapes, seed)
    jax.block_until_ready(params)
    return params


class CompileClock:
    """Sums XLA backend-compile seconds and counts compilations (JAX's own
    monitoring event; a persistent-cache hit compiles nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def enable_cache() -> str:
    """JAX's persistent compilation cache, where the program keeps it
    (``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``), with
    every program cached, so that only a cell's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    where = enable_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def device_line() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _annotate(obj, attr: str, name: str) -> None:
    import jax

    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)

    setattr(obj, attr, wrapped)


# ------------------------------------------------------------------ serving

@dataclasses.dataclass
class Served:
    sessions: list[dict]           # prompt, out (every served token), rid
    step_spans: list[tuple[float, float]]
    step_lengths: list[list[int]]  # keys each running slot attends, per step
    gaps_s: list[float]            # inter-token gaps inside the window
    tokens: int                    # output tokens of the window
    window_s: float
    setup_end: float               # perf_counter at the window's start
    compile_s: float               # compile seconds inside the window
    compiles: int
    attempted: int
    failed: int
    peak_bytes: int
    params_bytes: int
    pool_bytes: int


def serve(cell: Cell, eng, params, seed: int, seconds: float, *,
          clock: CompileClock, trace_dir: str | None = None) -> Served:
    """Bring the cell's sessions resident, then drive the scheduler for
    ``seconds``.  Returns what the window served and how long it took."""
    import jax

    from bench import weights
    from repro.serving import ContinuousScheduler, Request

    dep = cell.deployment
    gen = traffic_module(cell.mix["kind"])
    reqs = [Request(rid=i, tokens=r["tokens"].tolist(), max_new=r["max_new"])
            for i, r in enumerate(gen.generate(cell.mix, cell.config["vocab_size"], seed))]
    sched = ContinuousScheduler(eng, params, chunk_tokens=dep["chunk_tokens"])
    sched.start()
    for r in reqs:
        sched.submit(r)
    # set-up: every session prefilled through the scheduler's own chunked
    # admission (earlier sessions decode meanwhile), then a few pure
    # decode steps, so every program the window runs is compiled
    while len(sched.running) < len(reqs):
        if not sched.step():
            raise RuntimeError("scheduler stalled while admitting the sessions")
        if sched.outcomes:
            raise RuntimeError(f"a session ended in set-up: {sched.outcomes}")
    for _ in range(WARM_STEPS):
        sched.step()
    pool_bytes = weights.nbytes(sched._cache)
    if trace_dir is not None:
        _annotate(eng, "decode", "bench.decode_dispatch")
        _annotate(eng, "advance_slot", "bench.advance_slot")
        _annotate(sched, "step", "bench.step")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # the harness's spans suffice
        jax.profiler.start_trace(trace_dir, profiler_options=opts)

    c_s, c_n = clock.seconds, clock.count
    seen = {r.rid: len(r.out) for r in reqs}
    t_start = time.perf_counter()
    last = {r.rid: t_start for r in reqs}
    spans, lengths, gaps = [], [], []
    tokens = 0
    while True:
        ts = time.perf_counter()
        if ts - t_start >= seconds:
            break
        lengths.append([len(r.tokens) + len(r.out) for r in sched.running.values()])
        sched.step()
        te = time.perf_counter()
        spans.append((ts, te))
        for r in reqs:             # a decode step serves one token a session
            if len(r.out) > seen[r.rid]:
                gaps.append(te - last[r.rid])
                tokens += len(r.out) - seen[r.rid]
                seen[r.rid] = len(r.out)
                last[r.rid] = te
    window = spans[-1][1] - t_start
    if trace_dir is not None:
        jax.profiler.stop_trace()
    failed = sum(oc.status != "finished" for oc in sched.outcomes.values())
    served = Served(
        sessions=[{"rid": r.rid, "prompt": r.tokens, "out": list(r.out)} for r in reqs],
        step_spans=spans, step_lengths=lengths, gaps_s=gaps, tokens=tokens,
        window_s=window, setup_end=t_start, compile_s=clock.seconds - c_s,
        compiles=clock.count - c_n, attempted=len(reqs), failed=failed,
        peak_bytes=peak_bytes(), params_bytes=weights.nbytes(params),
        pool_bytes=pool_bytes,
    )
    # free the served state before the reference runs on the device
    sched._cache = None
    del sched
    gc.collect()
    return served


# --------------------------------------------------------------- per-layer

@dataclasses.dataclass
class RunData:
    """What a per-layer metric reader reads."""
    trace: object | None           # trace_reduce.Reduced
    step_work: list[dict]          # bench.work.step() of every traced step
    peak: dict                     # peaks.json entry of the device


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    table = _json(root / "bench" / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


def per_layer(cell: Cell, red, step_lengths: list[list[int]], peak: dict) -> dict:
    """The cell's per-layer metrics from a reduced trace (trace_reduce.
    Reduced) and the keys each running slot attended at every traced step.
    A reader that finds nothing to read returns None, and its metric is
    left out of the result (and named on standard error)."""
    from bench import work

    shapes = work.Shapes.of(cell.config, cell.deployment)
    weights = cell.block.arch.weight_work
    data = RunData(red, [work.step(shapes, L, weights(cell.config, len(L)))
                         for L in step_lengths], peak)
    metrics = {}
    for m in cell.per_layer:
        v = metric_module(m["name"]).read(data)
        if v is None:
            print(f"bench: per-layer metric {m['name']} found nothing to read",
                  file=sys.stderr, flush=True)
        else:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return metrics


# -------------------------------------------------------------------- a run

def run(cell: Cell, seed: int, seconds: float, trace: bool, *, t0: float,
        root: Path = ROOT, fault=None, control: bool = False, engine=None) -> dict:
    """One run; returns the result line's object.

    ``fault(engine)``, for tests, breaks the timed path underneath before
    set-up starts.  ``control`` puts the float8 control in the program's
    place in the comparison: the tokens checked are the ones the control
    puts first at each served position, so a sound limit reads the run as
    not correct.  ``engine``: an engine built for this cell, to reuse."""
    from bench import correctness, trace_reduce

    dev = device_line()
    log(f"device: {dev}")
    clock = CompileClock()
    eng = build_engine(cell) if engine is None else engine
    if fault is not None:
        fault(eng)
    params = make_params(eng, seed)
    trace_dir = str(root / TRACE_DIR / cell.name) if trace else None
    served = serve(cell, eng, params, seed, seconds, clock=clock, trace_dir=trace_dir)
    setup_s = served.setup_end - t0
    log(f"set-up s {setup_s:.3f}; compile s in set-up "
        f"{clock.seconds - served.compile_s:.3f}")
    log(f"window s {served.window_s:.6f}: {len(served.step_spans)} steps, "
        f"{served.tokens} tokens; compile s inside the window "
        f"{served.compile_s:.6f} ({served.compiles} compilations)")
    steps = np.array([e - s for s, e in served.step_spans]) * 1e3
    slow = np.argsort(steps)[::-1][:5]
    log(f"step ms: median {np.median(steps):.3f}; slowest (step, ms) "
        f"{[(int(i), round(float(steps[i]), 3)) for i in slow]}")
    log(f"itl samples {len(served.gaps_s)} (p95 over them); sessions "
        f"{served.attempted}, failed {served.failed}")
    log(f"peak_bytes_in_use {served.peak_bytes}; params bytes "
        f"{served.params_bytes}; pool bytes {served.pool_bytes}")
    dev["memory_peak_bytes"] = served.peak_bytes
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    breakdown = None
    if trace:
        red = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_xplane(trace_dir)))
        metrics = per_layer(cell, red, served.step_lengths, peaks_for(dev["kind"], root))
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        breakdown = red.breakdown()
        log(f"idle by harness span (s): {breakdown.pop('idle_by_span')}")
    else:
        metrics = {
            "decode_tokens_per_s": served.tokens / served.window_s,
            "itl_p95_ms": float(np.percentile(served.gaps_s, 95)) * 1e3,
            "setup_s": setup_s,
        }
        metrics = {k: {"value": v, "unit": units[k]} for k, v in metrics.items()}
    # the comparison, once the window is closed and its state freed
    t_ref = time.perf_counter()
    picked = correctness.sample(served.sessions, cell.mix["checked_sessions"], seed)
    gaps = correctness.gaps(cell.block, cell.config, cell.deployment, params, picked,
                            lowp=control)
    gap = float(max(g.max() for g in gaps))
    n_checked = int(sum(len(g) for g in gaps))
    log(f"reference s {time.perf_counter() - t_ref:.3f} over {len(picked)} "
        f"sessions, {n_checked} served tokens"
        + ("; the float8 control's tokens in the program's place" if control else ""))
    limit = cell.limits["logit_gap_max"]
    checks = {"logit_gap_max": {"value": gap, "limit": limit}}
    correct = bool(gap <= limit and served.failed == 0 and served.tokens > 0)
    print(f"check logit_gap_max: {gap!r} (limit {limit!r}) over {n_checked} "
          f"served tokens of {len(picked)} sessions", file=sys.stderr, flush=True)
    out = {"correct": correct, "attempted": served.attempted,
           "failed": served.failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out
