"""bench/serve_spans.py: the scheduler's ``serve.*`` spans on a hand-built
trace, on a real profile of a tiny paged engine on the CPU, and on a trace
recorded on the chip."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import gc
import gzip
from pathlib import Path

import pytest

from bench import serve_spans as ss
from bench import trace_reduce as tr
from bench.serve_spans import Span
from bench.trace_reduce import Event, Trace

DATA = Path(__file__).parent / "data"
MS = 1e6


def _hand_built():
    """One 100-ms step: the device runs 20-60 and 90-100 ms; it idles in
    the decode dispatch (the harness's span and a compile inside it), the
    token sync, the retire loop and the step's own code."""
    spans = [Span("bench.step", 0, 100 * MS),
             Span("serve.step", 1 * MS, 99 * MS, (("step", 7), ("running", 4))),
             Span("serve.append_capacity", 1 * MS, 4 * MS),
             Span("serve.decode_dispatch", 5 * MS, 20 * MS),
             Span("bench.decode_dispatch", 5 * MS, 19 * MS),
             Span("backend_compile_and_load", 8 * MS, 18 * MS),
             Span("serve.token_sync", 20 * MS, 70 * MS),
             Span("serve.retire", 70 * MS, 80 * MS)]
    ops = {0: [Event("fusion.1", 20 * MS, 60 * MS), Event("fusion.2", 90 * MS, 100 * MS)]}
    return Trace(ops, {}, []), spans


def test_idle_goes_to_the_innermost_span():
    trace, spans = _hand_built()
    got = ss.idle(trace, spans)
    assert got["window_s"] == pytest.approx(0.1)
    assert got["idle_s"] == pytest.approx(0.05)
    assert got["idle_by_span"] == pytest.approx({
        "bench.step": 1e-3, "serve.step": 0.011, "serve.append_capacity": 3e-3,
        "bench.decode_dispatch": 4e-3, "serve.decode_dispatch": 1e-3,
        "backend_compile_and_load": 0.010, "serve.token_sync": 0.010,
        "serve.retire": 0.010})
    # the phases below serve.step (the harness's span inside one too) and
    # the compile, not the step's own code
    assert got["explained_share"] == pytest.approx(0.038 / 0.05)
    # the two gaps of 10 ms or more, with the spans that held them and the
    # stats of the step around them
    first, second = got["long_gaps"]
    assert first["s"] == pytest.approx(0.030)
    assert first["spans"] == pytest.approx({"serve.token_sync": 0.010,
                                            "serve.retire": 0.010,
                                            "serve.step": 0.010})
    assert first["step"] == {"step": 7, "running": 4}
    assert second["s"] == pytest.approx(0.020)
    assert max(second["spans"], key=second["spans"].get) == "backend_compile_and_load"


def test_per_step_figures_on_the_hand_built_trace():
    _, spans = _hand_built()
    # the step less its token sync, and the append work, a step
    assert ss.host_serial_ms_per_step(spans) == pytest.approx(48.0)
    assert ss.append_capacity_ms_per_step(spans) == pytest.approx(3.0)
    assert ss.phase_ms_per_step(spans)["serve.decode_dispatch"] == pytest.approx(15.0)
    # nothing to read without the program's spans
    bench_only = [s for s in spans if s.name.startswith("bench.")]
    assert ss.host_serial_ms_per_step(bench_only) is None
    assert ss.append_capacity_ms_per_step(bench_only) is None


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """Three steps of a tiny paged engine with two resident requests,
    profiled on the CPU, with a forced collection between two of them."""
    import jax

    from repro.configs import reduced_config
    from repro.core.policy import PolicyConfig
    from repro.models import build_model
    from repro.serving import ContinuousScheduler, Engine, Request

    pol = PolicyConfig(kind="fier", budget=16, group=8, skip_layers=1,
                       pipeline="one_pass", layout="paged", block_size=8,
                       pool_blocks=24)
    bundle = build_model(reduced_config("olmo-1b"), pol)
    params = bundle.init(jax.random.PRNGKey(0))
    eng = Engine(bundle, n_slots=2, capacity=64)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    sched.start()
    for i in range(2):
        sched.submit(Request(rid=i, tokens=list(range(3 + i, 15 + i)), max_new=30))
    while len(sched.running) < 2:
        sched.step()
    for _ in range(2):                      # every program compiled
        sched.step()
    first_step, before = sched.steps, sum(eng.jit_cache_sizes().values())
    log_dir = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        sched.step()
        gc.collect()
        sched.step()
        sched.step()
    finally:
        jax.profiler.stop_trace()
    grown = sum(eng.jit_cache_sizes().values()) - before
    return first_step, grown, ss.load_spans(tr.find_xplane(log_dir))


def test_the_scheduler_spans_on_a_real_profile(cpu_profile):
    first_step, grown, spans = cpu_profile
    steps = sorted((s for s in spans if s.name == "serve.step"),
                   key=lambda s: s.start_ns)
    assert [s.stat("step") for s in steps] == [first_step + i for i in range(3)]
    assert all(s.stat("running") == 2 for s in steps)
    # the programs each step compiled or loaded (a block boundary's first
    # crossing brings new ones), and no others
    assert sum(s.stat("new_programs") for s in steps) == grown
    for step in steps:
        inner = {s.name for s in spans if s.name.startswith("serve.")
                 and step.start_ns <= s.start_ns and s.end_ns <= step.end_ns}
        assert {"serve.housekeeping", "serve.admit", "serve.append_capacity",
                "serve.decode_dispatch", "serve.token_sync", "serve.watchdog",
                "serve.retire"} <= inner
    gcs = [s for s in spans if s.name == "serve.gc"]
    assert gcs and any(s.stat("generation") == 2 for s in gcs)
    assert ss.host_serial_ms_per_step(spans) > 0
    assert ss.append_capacity_ms_per_step(spans) > 0


@pytest.fixture(scope="module")
def chip_recording(tmp_path_factory):
    """Two seconds of olmo-1b.longctx_decode (3 decode steps) traced on a
    TPU v5e with the scheduler's spans, by ``bench/run.py --seed
    3000000003 --seconds 2 --trace 1``."""
    path = tmp_path_factory.mktemp("xplane") / "run.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "longctx_decode_2s_spans.xplane.pb.gz").read_bytes()))
    return tr.load(str(path)), ss.load_spans(str(path))


def test_the_spans_on_the_chip_recording(chip_recording):
    trace, spans = chip_recording
    steps = [s for s in spans if s.name == "serve.step"]
    assert len(steps) == 3 and all(s.stat("running") == 4 for s in steps)
    assert sum(s.stat("new_programs") for s in steps) == 0
    assert ss.host_serial_ms_per_step(spans) == pytest.approx(3.8381636666666665)
    assert ss.append_capacity_ms_per_step(spans) == pytest.approx(0.017399666666666667)
    got = ss.idle(trace, spans)
    assert got["window_s"] == pytest.approx(2.103164917)
    assert got["idle_s"] == pytest.approx(0.015318426)
    # the spans below serve.step name 97% of the device's idle time: the
    # decode launch, the in-program gaps under the token sync, the logits
    # readback
    assert got["explained_share"] == pytest.approx(0.970889110930849)
    top = list(got["idle_by_span"])[:3]
    assert top == ["serve.decode_dispatch", "serve.token_sync", "serve.watchdog"]
    assert got["long_gaps"] == []


def test_the_readers_on_the_chip_recording(chip_recording):
    """Every per-layer metric of the cell reads the recording with the
    spans in it, as it reads the recording without them."""
    from bench import harness

    trace, _ = chip_recording
    cell = harness.load_cell("olmo-1b.longctx_decode")
    lengths = [[p + 64 + i for p in (7552, 8320, 9088, 9984)] for i in range(3)]
    got = harness.per_layer(cell, tr.reduce(trace), lengths,
                            harness.peaks_for("TPU v5 lite"))
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert got["decode_step_ms"]["value"] == pytest.approx(695.942562)
    assert got["device_idle_share"]["value"] == pytest.approx(0.7283511566867684)
    assert got["host_ms_per_step"]["value"] == pytest.approx(5.095095333333333)
    for m in cell.per_layer:
        if m["unit"] == "%":
            assert 0 < got[m["name"]]["value"] < 100
