"""bench/trace_reduce.py on hand-built events and on a trace recorded on
the chip."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace

DATA = Path(__file__).parent / "data"


def _trace():
    # two steps on the host, 0-100 and 100-200 ns; device ops overlap
    spans = [Event("bench.step", 0, 100), Event("bench.step", 100, 200),
             Event("bench.decode_dispatch", 5, 20),
             Event("bench.advance_slot", 120, 150)]
    ops = {0: [Event("fusion.1", 10, 40), Event("retrieve", 30, 60),
               Event("fusion.1", 150, 190), Event("attend", 185, 195)]}
    modules = {0: [Event("jit__decode_active_impl", 10, 60),
                   Event("jit__decode_active_impl", 150, 195)]}
    return Trace(ops, modules, spans)


def test_merge_and_covered():
    merged = tr.merge([(30, 60), (10, 40), (150, 190), (185, 195), (70, 70)])
    assert merged == [(10, 60), (150, 195)]
    assert tr.covered(merged, 0, 100) == 50
    assert tr.covered(merged, 50, 160) == 20


def test_reduce_busy_idle_ops_and_programs():
    red = tr.reduce(_trace())
    assert red.window_ns == (0, 200)
    assert red.busy_ns == 50 + 45                 # overlapping ops count once
    assert red.window_s == pytest.approx(200e-9)
    assert red.op_ns == {"fusion.1": 70, "retrieve": 30, "attend": 10}
    assert red.step_busy_ns == [50, 45]
    assert red.program_s("decode_active") == [pytest.approx(50e-9), pytest.approx(45e-9)]
    assert red.kernel_s("retrieve") == pytest.approx(30e-9)


def test_gaps_go_to_the_innermost_span():
    red = tr.reduce(_trace())
    # idle: 0-10 (inside decode_dispatch at 5? midpoint 5 -> dispatch),
    # 60-150 (midpoint 105 -> step), 195-200 (midpoint 197.5 -> step)
    assert sorted(red.gaps) == sorted([(10, "bench.decode_dispatch"),
                                       (90, "bench.step"), (5, "bench.step")])
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(70e-9)]
    assert bd["idle_gaps"][0] == ["bench.step", pytest.approx(90e-9)]
    assert dict(bd["idle_by_span"])["bench.step"] == pytest.approx(95e-9)


def test_reduce_needs_steps_and_device_ops():
    with pytest.raises(ValueError):
        tr.reduce(Trace({0: []}, {}, [Event("bench.step", 0, 1)]))
    with pytest.raises(ValueError):
        tr.reduce(Trace({0: [Event("x", 0, 1)]}, {}, []))


def test_device_figures_average_over_devices():
    t = _trace()
    t.ops[1] = [Event("fusion.1", 0, 200)]
    red = tr.reduce(t)
    assert red.busy_ns == (95 + 200) / 2


def test_nested_ops_count_their_own_time():
    # a while op on the device holds the ops of its body on the same line
    ops = [Event("while.3", 0, 100), Event("retrieve", 10, 50),
           Event("fusion.2", 50, 90), Event("fusion.2", 120, 130),
           Event("attend", 95, 125)]          # overlaps while.3, not nested
    assert tr.self_times(ops) == [20, 40, 40, 10, 30]
    red = tr.reduce(Trace({0: ops}, {}, [Event("bench.step", 0, 130)]))
    assert red.op_ns == {"while.3": 20, "retrieve": 40, "fusion.2": 50,
                         "attend": 30}


@pytest.fixture(scope="module")
def chip_trace(tmp_path_factory):
    """Two seconds of olmo-1b.longctx_decode (3 decode steps) traced on a
    TPU v5e by ``bench/run.py --seed 3000000003 --seconds 2 --trace 1``."""
    path = tmp_path_factory.mktemp("xplane") / "run.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "longctx_decode_2s.xplane.pb.gz").read_bytes()))
    return tr.load(str(path))


def test_recorded_chip_trace(chip_trace):
    red = tr.reduce(chip_trace)
    assert len(red.step_spans) == 3
    assert red.window_s == pytest.approx(2.102514472)
    assert red.busy_s == pytest.approx(2.08804856)
    # one execution of the decode-step program a step
    steps = red.program_s("decode_active_impl")
    assert len(steps) == 3 and all(0.69 < t < 0.70 for t in steps)
    # the two FIER kernels, by their short op names
    assert not any(" = " in op for op in red.op_ns)
    assert red.kernel_s("paged_fused_retrieve_hm") == pytest.approx(1.383194652)
    assert red.kernel_s("paged_fused_sparse_attention_hm") == pytest.approx(0.519036697)
    # ops nest only in the layer loops' while ops: self times add up to no
    # more than the busy time
    assert sum(red.op_ns.values()) / 1e9 <= red.busy_s * 1.001
    top = red.breakdown()["device_ops"][0][0]
    assert top.startswith("%paged_fused_retrieve_hm")


def test_readers_on_the_recorded_chip_trace(chip_trace):
    from bench import harness

    cell = harness.load_cell("olmo-1b.longctx_decode")
    # the resident contexts at the traced steps, prompts plus served tokens
    lengths = [[p + 64 + i for p in (7552, 8320, 9088, 9984)] for i in range(3)]
    got = harness.per_layer(cell, tr.reduce(chip_trace), lengths,
                            harness.peaks_for("TPU v5 lite"))
    assert set(got) == {m["name"] for m in cell.per_layer}
    assert got["decode_step_ms"]["value"] == pytest.approx(696.009918)
    assert got["device_idle_share"]["value"] == pytest.approx(0.6880291285814466)
    for m in cell.per_layer:
        if m["unit"] == "%":
            assert 0 < got[m["name"]]["value"] < 100
