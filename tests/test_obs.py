"""Observability subsystem: metrics registry, span tracing, retrieval
introspection — plus the zero-overhead guarantees of the disabled path."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.policy import PolicyConfig
from repro.models import build_model
from repro.obs import (
    MetricsRegistry,
    Observability,
    Snapshot,
    Tracer,
    derive_serving_metrics,
    load_trace_events,
    parse_prometheus_text,
    validate_chrome_trace,
)
from repro.obs.tracing import PID_REQUEST, _percentile
from repro.serving import (
    ContinuousScheduler,
    Engine,
    FaultSpec,
    Request,
    ServingFaultInjector,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OBS_TOOL = os.path.join(REPO, "tools", "obs_report.py")
REG_TOOL = os.path.join(REPO, "tools", "check_bench_regression.py")


# ------------------------------------------------------------ registry units

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests")
    c.inc()
    c.inc(2, status="finished")
    assert c.value() == 1.0
    assert c.value(status="finished") == 2.0
    g = reg.gauge("depth")
    g.set(4)
    g.add(-1)
    assert g.value() == 3.0
    h = reg.histogram("lat", buckets=(1.0, 10.0))
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count() == 3 and h.sum() == 55.5
    assert h.mean() == pytest.approx(18.5)
    # create-or-return: same instrument object, kind mismatch raises
    assert reg.counter("req_total") is c
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("req_total")


def test_counter_rejects_negative_and_gate_needs_direction():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="negative"):
        reg.counter("c").inc(-1)
    with pytest.raises(ValueError, match="direction"):
        reg.gauge("g", gate=True)


def test_disabled_registry_is_noop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("x")
    c.inc(5)
    assert c.value() == 0.0
    reg.gauge("y").set(3)
    reg.histogram("z").observe(1)
    assert reg.snapshot().series == []
    # one shared null instrument — no per-call allocation
    assert reg.counter("a") is reg.gauge("b")


def test_snapshot_diff_counters_subtract_gauges_keep_level():
    reg = MetricsRegistry()
    c, g, h = reg.counter("c"), reg.gauge("g"), reg.histogram("h", buckets=(1.0,))
    c.inc(3)
    g.set(10)
    h.observe(0.5)
    older = reg.snapshot()
    c.inc(4)
    g.set(2)
    h.observe(7.0)
    d = reg.snapshot().diff(older)
    assert d.value("c") == 4.0
    assert d.value("g") == 2.0
    hs = d.get("h")
    assert hs.count == 1 and hs.value == 7.0 and hs.bucket_counts == (0, 1)


def test_snapshot_json_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.counter("c", "help", unit="tok").inc(2, mode="x")
    reg.gauge("g", better="lower", gate=True).set(1.5)
    reg.histogram("h", buckets=(1.0, 2.0)).observe(1.7)
    doc = reg.write_snapshot_json(str(tmp_path / "snap.json"))
    with open(tmp_path / "snap.json") as f:
        assert json.load(f) == doc
    back = Snapshot.from_json(doc)
    assert back.to_json() == doc
    assert back.value("c", mode="x") == 2.0
    assert back.get("g").gate is True


def test_prometheus_roundtrip():
    reg = MetricsRegistry()
    reg.counter("c").inc(3, mode="a")
    reg.gauge("g").set(0.25)
    h = reg.histogram("h", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(1.5)
    h.observe(9.0)
    text = reg.snapshot().to_prometheus_text()
    flat = parse_prometheus_text(text)
    assert flat['c{mode="a"}'] == 3.0
    assert flat["g"] == 0.25
    assert flat['h_bucket{le="1.0"}'] == 1.0
    assert flat['h_bucket{le="2.0"}'] == 2.0
    assert flat['h_bucket{le="+Inf"}'] == 3.0
    assert flat["h_sum"] == 11.0 and flat["h_count"] == 3.0


# ------------------------------------------------------------- tracing units

def _synthetic_tracer():
    tr = Tracer()
    tr.instant("submitted", ts=0.0, pid=PID_REQUEST, tid=0, cat="lifecycle")
    tr.instant("submitted", ts=5.0, pid=PID_REQUEST, tid=1, cat="lifecycle")
    tr.complete("prefill", 0.0, 8.0, pid=PID_REQUEST, tid=0, slot=0)
    for t in (10.0, 12.0, 14.0):
        tr.instant("token", ts=t, pid=PID_REQUEST, tid=0, cat="decode")
    tr.instant("token", ts=20.0, pid=PID_REQUEST, tid=1, cat="decode")
    tr.counter("occupancy", {"running": 2.0}, ts=14.0)
    return tr


def test_chrome_export_validates_and_roundtrips(tmp_path):
    tr = _synthetic_tracer()
    doc = tr.write_chrome_trace(str(tmp_path / "t.trace.json"))
    with open(tmp_path / "t.trace.json") as f:
        assert json.load(f) == doc
    assert validate_chrome_trace(doc) == []
    back = load_trace_events(doc)
    assert [(e.name, e.ph, e.ts, e.pid, e.tid, e.dur) for e in back] == [
        (e.name, e.ph, e.ts, e.pid, e.tid, e.dur) for e in tr.events]


def test_span_is_a_profiler_annotation_and_adds_no_event():
    """``tracer.span`` is a wall-clock span on the profiler's clock, the
    same on the enabled and the null tracer; the virtual-clock event
    buffer never sees it."""
    from repro.obs.tracing import NULL_TRACER

    tr = Tracer()
    for t in (tr, NULL_TRACER):
        with t.span("serve.step", step=3) as sp:
            assert isinstance(sp, jax.profiler.TraceAnnotation)
    assert tr.events == []


def test_obs_imports_without_jax():
    """The stdlib-only tools (tools/obs_report.py, tools/check_bench_
    regression.py) import repro.obs on machines without JAX: the spans
    load it only when one is opened."""
    code = (
        "import sys; sys.modules['jax'] = None\n"
        "from repro.obs import Observability, Tracer\n"
        "obs = Observability(); obs.tracer.instant('x')\n"
        "assert len(obs.tracer.events) == 1 and 'jax' not in "
        "[m for m in sys.modules if sys.modules[m] is not None]\n"
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr


def test_validate_catches_malformed():
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": [{"name": "x"}]}) != []
    bad_dur = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 0, "pid": 0, "tid": 0}]}
    assert any("dur" in e for e in validate_chrome_trace(bad_dur))
    bad_counter = {"traceEvents": [
        {"name": "x", "ph": "C", "ts": 0, "pid": 0, "tid": 0,
         "args": {"v": "nan?"}}]}
    assert any("numeric" in e for e in validate_chrome_trace(bad_counter))


def test_derive_serving_metrics_synthetic():
    d = derive_serving_metrics(_synthetic_tracer())
    assert d["n_requests"] == 2 and d["total_tokens"] == 4
    # TTFTs are [10, 15] → p50 linearly interpolated
    assert d["ttft_p50"] == pytest.approx(12.5)
    assert d["itl_p50"] == 2.0
    assert d["makespan"] == 20.0
    assert d["tokens_per_kunit"] == pytest.approx(200.0)


def test_percentile_matches_numpy_bitwise():
    rng = np.random.default_rng(0)
    for n in (1, 2, 5, 17, 100):
        xs = sorted(rng.normal(size=n).tolist())
        for p in (0, 25, 50, 90, 99, 100):
            assert _percentile(xs, p / 100.0) == float(np.percentile(xs, p)), (n, p)


# ---------------------------------------------------- serving integration

@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("olmo-1b")

    def mk(pool_blocks=0):
        pol = PolicyConfig(
            kind="fier", budget=16, group=8, skip_layers=1,
            pipeline="one_pass",
            layout="paged" if pool_blocks else "slab",
            block_size=8, pool_blocks=pool_blocks,
        )
        return build_model(cfg, pol)

    slab = mk()
    params = slab.init(jax.random.PRNGKey(0))
    return cfg, mk, slab, params


def _reqs(n=3, max_new=5):
    return [Request(rid=i, tokens=list(range(3 + i, 11 + i)), max_new=max_new)
            for i in range(n)]


def test_disabled_obs_identical_outputs_and_no_extra_compiles(setup):
    """The overhead guard: an obs-enabled engine produces bit-identical
    outputs AND identical jit cache populations (zero extra recompiles)
    vs an engine with observability off."""
    cfg, mk, slab, params = setup
    runs = {}
    for label, obs in (("off", None), ("on", Observability())):
        eng = Engine(mk(pool_blocks=24), n_slots=2, capacity=64, obs=obs)
        sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
        out = sched.run(_reqs())
        runs[label] = (dict(out), eng.jit_cache_sizes())
    out_off, jits_off = runs["off"]
    out_on, jits_on = runs["on"]
    assert out_off == out_on
    assert jits_off == jits_on, (jits_off, jits_on)
    # and the disabled path really recorded nothing
    assert isinstance(jits_off, dict) and sum(jits_off.values()) > 0


def test_decode_program_keeps_the_name_the_trace_readers_match(setup):
    """The device trace names each execution of the batched decode step
    by its module, ``jit__decode_active_impl``; the benchmark's
    ``decode_step_ms`` reader matches ``decode_active_impl``."""
    import jax.numpy as jnp

    cfg, mk, slab, params = setup
    eng = Engine(mk(pool_blocks=24), n_slots=2, capacity=64)
    lowered = eng._decode_active.lower(
        params, jnp.zeros((2,), jnp.int32), eng.new_cache(),
        jnp.ones((2,), bool), jnp.full((2,), -1, jnp.int32))
    name = str(lowered.compiler_ir().operation.attributes["sym_name"])
    assert "decode_active_impl" in name, name


def test_trace_determinism_two_seeded_runs(setup):
    """Two identical seeded runs must produce identical virtual-clock
    traces (wall_ts excluded via canonical()) and identical snapshots."""
    cfg, mk, slab, params = setup

    def one_run():
        eng = Engine(mk(pool_blocks=24), n_slots=2, capacity=64,
                     obs=Observability())
        sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
        sched.run(_reqs())
        return (eng.obs.tracer.canonical(),
                eng.obs.metrics.snapshot().as_dict(),
                derive_serving_metrics(eng.obs.tracer))

    trace_a, snap_a, d_a = one_run()
    trace_b, snap_b, d_b = one_run()
    assert trace_a == trace_b
    assert snap_a == snap_b
    assert d_a == d_b
    assert d_a["total_tokens"] > 0 and d_a["ttft_p99"] > 0


def test_outcomes_carry_slot_and_preempt_events(setup):
    """Preemptions under oversubscription leave structured health events
    (slot, rid, reason) and every retirement records its slot."""
    cfg, mk, slab, params = setup
    eng = Engine(mk(pool_blocks=10), n_slots=3, capacity=64,
                 obs=Observability())
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    out = sched.run(_reqs(3, max_new=25))
    assert sched.preemptions > 0
    preempts = [e for e in sched.health.events if e["kind"] == "preempt"]
    assert preempts, sched.health.events
    for e in preempts:
        assert isinstance(e["slot"], int) and isinstance(e["rid"], int)
        assert e["reason"]
    for oc in out.outcomes.values():
        assert oc.status == "finished" and oc.slot is not None
    # the same preemptions landed on the trace and in the registry
    tr_preempts = [e for e in eng.obs.tracer.events if e.name == "preempt"]
    assert len(tr_preempts) == sched.preemptions
    assert eng.obs.metrics.counter("preemptions_total").value() == float(
        sched.preemptions)
    assert sched.health.summary()["events"] == len(sched.health.events)


def test_quarantine_and_fault_events(setup):
    """An injected poison-logits fault quarantines its slot: the outcome,
    the health event log, and the trace all agree."""
    cfg, mk, slab, params = setup
    inj = ServingFaultInjector([FaultSpec("poison_logits", step=2, rid=0)])
    eng = Engine(mk(pool_blocks=24), n_slots=2, capacity=64,
                 obs=Observability())
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16, injector=inj)
    out = sched.run(_reqs(2, max_new=20))
    assert inj.all_fired
    oc = out.outcomes[0]
    assert oc.status == "quarantined" and oc.slot is not None
    q_events = [e for e in sched.health.events if e["kind"] == "quarantine"]
    assert len(q_events) == 1 and q_events[0]["rid"] == 0
    names = [e.name for e in eng.obs.tracer.events]
    assert "fault" in names and "quarantine" in names
    assert eng.obs.metrics.counter("faults_injected_total").value(
        kind="poison_logits") == 1.0


def test_pool_stats_shim_matches_allocator_stats(setup):
    """Engine.pool_stats() is a naming shim over BlockAllocator.stats():
    every legacy key must alias a canonical series exactly."""
    cfg, mk, slab, params = setup
    eng = Engine(mk(pool_blocks=24), n_slots=2, capacity=64,
                 obs=Observability())
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    sched.run(_reqs())
    legacy, canon = eng.pool_stats(), eng.allocator.stats()
    assert legacy["blocks_in_use"] == canon["pool_blocks_in_use"]
    assert legacy["blocks_allocated"] == canon["pool_blocks_usable"]
    assert legacy["peak_in_use"] == canon["pool_peak_in_use"]
    assert legacy["prefix_block_hits"] == canon["pool_prefix_block_hits"]
    assert legacy["cow_copies"] == canon["pool_cow_copies"]
    assert legacy["utilization"] == canon["pool_utilization"]
    es = eng.engine_stats()
    assert legacy["prefills"] == es["engine_prefills"]
    assert legacy["budget_downshifts"] == es["engine_budget_downshifts"]
    # the sampled gauges carry the canonical names
    snap = eng.obs.metrics.snapshot()
    assert snap.value("pool_blocks_usable") == canon["pool_blocks_usable"]
    assert snap.value("engine_prefills") == es["engine_prefills"]


def test_introspector_records_bounded_quality_series(setup):
    """Opt-in retrieval introspection: probes land in the registry with
    ratio values in [0, 1] and budget utilization consistent with
    min(length, budget) / budget."""
    cfg, mk, slab, params = setup
    obs = Observability(introspect=True)
    eng = Engine(mk(pool_blocks=24), n_slots=2, capacity=64, obs=obs)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    sched.run(_reqs(2, max_new=8))
    recs = obs.introspector.records
    assert recs, "no probes taken"
    for r in recs:
        assert 0.0 <= r.oracle_overlap <= 1.0
        assert 0.0 <= r.recaptured_mass <= 1.0
        assert r.budget_utilization == pytest.approx(
            min(r.length, r.budget) / r.budget)
        assert np.isfinite(r.tau)
    snap = obs.metrics.snapshot()
    fier = {s.name for s in snap.series if s.name.startswith("fier_")}
    assert {"fier_oracle_overlap", "fier_recaptured_mass",
            "fier_budget_utilization", "fier_tau",
            "fier_probes_total"} <= fier
    assert snap.value("fier_probes_total") == float(len(recs))
    # probes also land on the trace as counter rows
    assert any(e.name.startswith("introspect/")
               for e in obs.tracer.events)


def test_introspection_skips_probe_layer_outside_rest_stack(setup):
    """A probe layer beyond the rest (retrieval-policy) stack must yield
    no records instead of indexing out of range — the reduced config has
    a single rest layer, so layer 99 exercises the guard."""
    cfg, mk, slab, params = setup
    obs = Observability(introspect=True, probe_layer=99)
    eng = Engine(slab, n_slots=1, capacity=64, obs=obs)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    sched.run(_reqs(1))
    assert obs.introspector.records == []
    assert obs.metrics.snapshot().value("fier_probes_total") == 0.0


# -------------------------------------------------------------- tool lanes

def _trace_file(tmp_path, name="t.trace.json"):
    path = str(tmp_path / name)
    _synthetic_tracer().write_chrome_trace(path)
    return path


def test_obs_report_validate_and_report(tmp_path):
    good = _trace_file(tmp_path)
    reg = MetricsRegistry()
    reg.gauge("vt_ttft_p99", better="lower", gate=True).set(100.0)
    snap = str(tmp_path / "METRICS_demo.json")
    reg.write_snapshot_json(snap)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, OBS_TOOL, "--validate", good, snap],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run([sys.executable, OBS_TOOL, good, snap],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "span-derived serving metrics" in r.stdout
    assert "vt_ttft_p99" in r.stdout and "[gated]" in r.stdout


def test_obs_report_validate_fails_on_malformed(tmp_path):
    path = _trace_file(tmp_path)
    with open(path) as f:
        doc = json.load(f)
    for row in doc["traceEvents"]:
        row.pop("ph", None)
    bad = str(tmp_path / "bad.trace.json")
    with open(bad, "w") as f:
        json.dump(doc, f)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, OBS_TOOL, "--validate", bad],
                       capture_output=True, text=True, env=env)
    assert r.returncode == 1
    assert "INVALID" in r.stderr


def _snapshot_doc(dirpath, value):
    reg = MetricsRegistry()
    reg.gauge("vt_ttft_p99", unit="unit", better="lower", gate=True).set(value)
    reg.counter("info_counter").inc(3)
    os.makedirs(dirpath, exist_ok=True)
    reg.write_snapshot_json(os.path.join(dirpath, "METRICS_demo.json"))


def test_regression_tool_gates_snapshot_format(tmp_path):
    """check_bench_regression reads METRICS_*.json registry snapshots:
    gated series within tolerance pass, a +30% latency regression fails."""
    _snapshot_doc(tmp_path / "base", 100.0)
    _snapshot_doc(tmp_path / "ok", 115.0)     # +15% < +20%
    _snapshot_doc(tmp_path / "bad", 130.0)    # +30% > +20%
    run = lambda new: subprocess.run(
        [sys.executable, REG_TOOL, "--baseline-dir", str(tmp_path / "base"),
         "--new-dir", str(new)], capture_output=True, text=True)
    r = run(tmp_path / "ok")
    assert r.returncode == 0, r.stdout + r.stderr
    r = run(tmp_path / "bad")
    assert r.returncode == 1
    assert "vt_ttft_p99" in r.stderr
