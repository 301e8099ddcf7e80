"""Every per-layer metric of every cell, read through ``harness.per_layer``
from a reduced trace built by hand: each reader reads the fields that
``RunData`` has and returns a number in its range."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import json
from pathlib import Path

import pytest

from bench import harness
from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAK = harness.peaks_for("TPU v5 lite", ROOT)
MS = 1e6


def _trace(n_steps: int = 3) -> Trace:
    """Steps of 100 ms on the host, each running the decode-step program
    for 90 ms: retrieval 40 ms, attend 30 ms, other ops 20 ms."""
    spans, ops, mods = [], [], []
    for i in range(n_steps):
        t = i * 100 * MS
        spans += [Event("bench.step", t, t + 100 * MS),
                  Event("bench.decode_dispatch", t, t + 2 * MS)]
        mods.append(Event("jit__decode_active_impl", t + 5 * MS, t + 95 * MS))
        ops += [Event("paged_fused_retrieve_hm", t + 5 * MS, t + 45 * MS),
                Event("paged_fused_sparse_attention_hm", t + 45 * MS, t + 75 * MS),
                Event("fusion.12", t + 75 * MS, t + 95 * MS)]
    return Trace({0: ops}, {0: mods}, spans)


def _cases():
    return [(w["name"], m["name"]) for w in SPEC["workloads"]
            for m in harness.load_cell(w["name"], ROOT).per_layer]


@pytest.mark.parametrize("workload,metric", _cases())
def test_reader_reads_a_number(workload, metric):
    cell = harness.load_cell(workload, ROOT)
    L = [cell.deployment["capacity"] // 2] * cell.mix["sessions"]
    got = harness.per_layer(cell, tr.reduce(_trace()), [L] * 3, PEAK)
    assert metric in got, f"{metric} read nothing"
    m = next(x for x in cell.per_layer if x["name"] == metric)
    assert got[metric]["unit"] == m["unit"]
    v = got[metric]["value"]
    assert v > 0
    if m["unit"] == "%":
        assert v <= 100


def test_readers_on_the_hand_built_trace():
    cell = harness.load_cell(SPEC["workloads"][0]["name"], ROOT)
    L = [cell.deployment["capacity"] // 2] * cell.mix["sessions"]
    got = harness.per_layer(cell, tr.reduce(_trace()), [L] * 3, PEAK)
    assert got["decode_step_ms"]["value"] == pytest.approx(90.0)
    # busy 90 of every 100 ms
    assert got["device_idle_share"]["value"] == pytest.approx(10.0)
    assert got["host_ms_per_step"]["value"] == pytest.approx(10.0)


def test_a_reader_with_nothing_to_read_is_left_out(capsys):
    cell = harness.load_cell(SPEC["workloads"][0]["name"], ROOT)
    t = _trace()
    t.ops[0] = [e for e in t.ops[0] if "retrieve" not in e.name]
    got = harness.per_layer(cell, tr.reduce(t), [[1000]] * 3, PEAK)
    assert "retrieve_roofline" not in got and "attend_roofline" in got
    assert "retrieve_roofline found nothing" in capsys.readouterr().err
