"""A configuration with a block unlike OLMo's comes into the benchmark as
new files and new entries only.

The block (``tests/bench/data/rms_block``: RMSNorm with gains, GQA 2:1,
an untied LM head) is laid over a copy of the benchmark in a temporary
root: its configuration file, architecture module, reference, mix and
limits, and its entries in BENCHMARK.json.  No file of the copy is
changed.  The harness then builds the program from it, draws its
weights, counts its work and decides ``correct`` against its own
reference, on the CPU, with the Pallas kernels in interpret mode.
"""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import json
import shutil
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness, work
from bench import trace_reduce as tr
from bench.trace_reduce import Event, Trace

ROOT = Path(__file__).resolve().parents[2]
BLOCK_FILES = Path(__file__).resolve().parent / "data" / "rms_block"
CELL = "tiny-rms.tiny_decode"
SEED = 2**31 + 23


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for f in (BLOCK_FILES / "bench").rglob("*"):
        if f.is_file():
            dest = root / f.relative_to(BLOCK_FILES)
            assert not dest.exists(), f"{dest} is not a new file"
            dest.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(f, dest)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    new = json.loads((BLOCK_FILES / "entries.json").read_text())
    spec["configs"] += new["configs"]
    spec["workloads"] += new["workloads"]
    for m in spec["per_layer"]:
        m["workloads"] = m["workloads"] + [CELL]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(CELL, root)


def _run(cell, fault=None):
    return harness.run(cell, SEED, 1.0, False, t0=time.perf_counter(), root=cell.root,
                       fault=fault)


def test_the_block_resolves_from_its_own_files(root, cell):
    assert Path(cell.block.arch.__file__) == root / "bench/arch/tiny-rms.py"
    assert Path(cell.block.reference.__file__) == root / "bench/reference/rms_decoder.py"
    cfg = harness.model_config(cell.config, root)
    assert (cfg.norm, cfg.tie_embeddings, cfg.n_heads, cfg.n_kv_heads) == ("rms", False, 4, 2)
    # olmo-1b's files in the same root are untouched and still resolve
    olmo = harness.load_cell("olmo-1b.longctx_decode", root)
    assert Path(olmo.block.arch.__file__) == root / "bench/arch/olmo-1b.py"


def test_gains_are_drawn_and_not_ones(cell):
    params = harness.make_params(harness.build_engine(cell), SEED)
    gains = np.concatenate([np.ravel(params["final_norm"]["w"]),
                            np.ravel(params["layers"]["norm1"]["w"]),
                            np.ravel(params["layers"]["norm2"]["w"])])
    assert gains.size == 7 * 128
    assert abs(gains.mean() - 1) < 0.02 and 0.08 < gains.std() < 0.12
    # the untied head is a matrix drawn at fan-in, not a gain
    head = np.asarray(params["lm_head"])
    assert head.shape[0] == 128 and abs(head.std() * 128**0.5 - 1) < 0.05


def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 2 and r["failed"] == 0


def altered_token(eng):
    """One token, of slot 0 at the fifth decode step, is altered where the
    step produces it."""
    real, calls = eng.decode, []

    def decode(params, tokens, cache, active=None, rng=None):
        nxt, logits, cache = real(params, tokens, cache, active=active, rng=rng)
        calls.append(1)
        if len(calls) == 5:
            nxt = nxt.at[0].set((nxt[0] + 1) % 512)
        return nxt, logits, cache

    eng.decode = decode


def test_altered_token_is_not_correct(cell):
    r = _run(cell, altered_token)
    assert not r["correct"], r["checks"]


def test_a_reference_that_forgets_the_gains_is_not_correct(root, cell):
    src = (root / "bench/reference/rms_decoder.py").read_text()
    assert src.count(" * gain\n") == 1
    (root / "bench/reference/rms_decoder_no_gain.py").write_text(
        src.replace(" * gain\n", "\n"))
    forgets = harness.Cell(cell.name, 1, dict(cell.config, reference="rms_decoder_no_gain"),
                           cell.mix, cell.limits, cell.end_to_end, [], root)
    r = _run(forgets)
    assert not r["correct"], r["checks"]


@pytest.mark.parametrize("key,value", [("reference", "decoder"), ("norm", "layernorm")])
def test_the_reference_must_compute_the_stated_block(root, cell, key, value):
    with pytest.raises(ValueError, match="computes"):
        harness.block(dict(cell.config, **{key: value}), root)


def test_a_block_the_program_lacks_is_refused(cell):
    with pytest.raises(ValueError, match="qk_norm"):
        cell.block.arch.model_config(dict(cell.config, qk_norm=True))


def test_work_counts_the_block(cell):
    # 3 layers of (q,k,v 128*(4+2+2)*32 + o 4*32*128 + SwiGLU 3*128*256),
    # and the untied head 512*128
    flops, nbytes = cell.block.arch.weight_work(cell.config, 2)
    assert nbytes == 2 * (3 * (32768 + 16384 + 98304) + 65536)
    assert flops == 2 * 2 * (3 * (32768 + 16384 + 98304) + 65536)
    # every per-layer reader reads the new cell from a trace of 2 steps
    ms = 1e6
    spans, ops, mods = [], [], []
    for i in range(2):
        t = i * 100 * ms
        spans.append(Event("bench.step", t, t + 100 * ms))
        mods.append(Event("jit__decode_active_impl", t + 5 * ms, t + 95 * ms))
        ops += [Event("paged_fused_retrieve_hm", t + 5 * ms, t + 45 * ms),
                Event("paged_fused_sparse_attention_hm", t + 45 * ms, t + 75 * ms)]
    peak = harness.peaks_for("TPU v5 lite", cell.root)
    red = tr.reduce(Trace({0: ops}, {0: mods}, spans))
    got = harness.per_layer(cell, red, [[300, 400]] * 2, peak)
    assert set(got) == {m["name"] for m in cell.per_layer}
    w = work.step(work.Shapes.of(cell.config, cell.deployment), [300, 400], (flops, nbytes))
    need = 2 * work.roofline_s(w["flops"], w["bytes"], peak)
    assert got["step_mfu"]["value"] == pytest.approx(100 * need / red.window_s)
