"""Every entry of BENCHMARK.json resolves by name to the files that
define it, and the file keeps to the benchmark's contract."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import importlib
import json
import re
from pathlib import Path

import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WORKLOADS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    for p in SPEC["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    script = ROOT / SPEC["command"][1]
    assert any(script.is_relative_to(ROOT / p) for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51


def test_names_and_units():
    entries = (SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"]
               + SPEC["per_layer"])
    for e in entries:
        assert NAME.match(e["name"]), e["name"]
    for group in ("configs", "workloads"):
        names = [e["name"] for e in SPEC[group]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                              "device_trace")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_workload_resolves(workload):
    cell = harness.load_cell(workload, ROOT)
    w = next(x for x in SPEC["workloads"] if x["name"] == workload)
    assert w["chips"] in (1, 4)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    # the configuration: its file, the reference it names, the program's block
    conf = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert cell.config["name"] == conf["name"]
    assert cell.config["reduced"] == conf["reduced"]
    importlib.import_module(f"bench.reference.{cell.config['reference']}")
    harness.model_config(cell.config)
    # the traffic: its mix file, the generator of its kind, the limits
    gen = harness.traffic_module(cell.mix["kind"])
    assert callable(gen.generate)
    assert set(cell.limits) == {"logit_gap_max"}
    # every per-layer metric of the cell has a reader, and moves a metric
    # the cell reports
    e2e = {m["name"] for m in cell.end_to_end}
    assert {"setup_s"} < e2e
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(harness.metric_module(m["name"]).read)
        assert m["moves"] in e2e


def test_every_config_is_used_and_every_metric_cell_exists():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    for m in SPEC["per_layer"] + SPEC["end_to_end"]:
        assert set(m.get("workloads", WORKLOADS)) <= set(WORKLOADS)


@pytest.mark.parametrize("workload", WORKLOADS)
def test_traffic_is_the_same_work_for_every_seed(workload):
    cell = harness.load_cell(workload, ROOT)
    gen = harness.traffic_module(cell.mix["kind"])
    vocab = cell.config["vocab_size"]
    a = gen.generate(cell.mix, vocab, 2**31 + 12345)
    b = gen.generate(cell.mix, vocab, 2**31 + 12345)
    c = gen.generate(cell.mix, vocab, 2**40 + 3)
    assert all(np.array_equal(x["tokens"], y["tokens"]) for x, y in zip(a, b))
    lens = lambda reqs: sorted(len(r["tokens"]) for r in reqs)
    assert lens(a) == lens(c)
    cap = cell.deployment["capacity"]
    for r in a + c:
        assert len(r["tokens"]) + r["max_new"] == cap
        assert r["tokens"].min() >= 0 and r["tokens"].max() < vocab
