"""Qwen3-MoE's block in the benchmark, on the CPU at a tiny size.

The configuration's own files (``bench/configs/qwen3-moe-235b-a22b.json``,
``bench/arch/qwen3-moe-235b-a22b.py``, ``bench/reference/moe_decoder.py``)
run in a copy of the benchmark that gains a cell of its own, with the
configuration shrunk: 2 layers, d 256, 8/2 heads, 16 experts routed top-4
with experts 4-7 held, 2 resident sessions.  The program serves through
``Engine`` and ``ContinuousScheduler`` (paged, one_pass, chunked
admission), with the Pallas kernels in interpret mode, and ``correct`` is
decided against the plain reference: true as served, false against a
reference without QK-norm or one that computes an expert held elsewhere.
"""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import json
import shutil
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, weights

ROOT = Path(__file__).resolve().parents[2]
CONFIG = "qwen3-moe-235b-a22b"
CELL = "qwen3-moe-235b-a22b.tiny_decode"
WORKLOAD = {"name": CELL, "config": CONFIG, "traffic": "tiny_moe_decode", "chips": 1,
            "why": "2 resident sessions at a CPU size"}
SEED = 2**33 + 16
TINY = {"num_hidden_layers": 2, "hidden_size": 256, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 32, "moe_intermediate_size": 128,
        "vocab_size": 512, "router_experts": 16, "num_experts": 4,
        "num_experts_per_tok": 4, "expert_first": 4}
TINY_MIX = {"kind": "resident_sessions", "max_new": "capacity_minus_prompt",
            "checked_sessions": 2,
            "deployment": {"slots": 2, "capacity": 512, "block_size": 32, "budget": 64,
                           "group": 32, "sink": 4, "recent": 16, "skip_layers": 1,
                           "chunk_tokens": 128},
            "sessions": 2,
            "prompt_tokens": {"lognormal_median": 300, "lognormal_sigma": 0.2, "min": 200,
                              "max": 400, "round_to": 32}}
# At this size a route that flips at a near-tie of the router (bf16
# activations in the program, f32 in the reference) moves a quarter of a
# token's expert output: sound runs read 0.61-1.15 on three seeds, a
# reference without QK-norm 2.02-3.66, one computing an expert held
# elsewhere 3.48-4.20 (CPU).
TINY_LIMIT = {"logit_gap_max": 1.5}


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"].append(WORKLOAD)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    conf = root / "bench/configs" / f"{CONFIG}.json"
    conf.write_text(json.dumps(dict(json.loads(conf.read_text()), **TINY)))
    (root / "bench/mixes/tiny_moe_decode.json").write_text(json.dumps(TINY_MIX))
    (root / "bench/cells" / f"{CELL}.json").write_text(json.dumps(TINY_LIMIT))
    return root


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(CELL, root)


def _run(cell):
    return harness.run(cell, SEED, 1.0, False, t0=time.perf_counter(), root=cell.root)


def _variant(root, cell, name, old, new):
    """The cell against a copy of its reference with ``old`` made ``new``."""
    src = (root / "bench/reference/moe_decoder.py").read_text()
    assert src.count(old) == 1, old
    (root / f"bench/reference/{name}.py").write_text(src.replace(old, new))
    return harness.Cell(cell.name, 1, dict(cell.config, reference=name), cell.mix,
                        cell.limits, cell.end_to_end, [], root)


def test_the_block_resolves_from_its_own_files(root, cell):
    assert Path(cell.block.arch.__file__) == root / f"bench/arch/{CONFIG}.py"
    assert Path(cell.block.reference.__file__) == root / "bench/reference/moe_decoder.py"
    cfg = harness.model_config(cell.config, root)
    assert (cfg.family, cfg.qk_norm, cfg.norm_eps, cfg.tie_embeddings) == ("moe", True,
                                                                          1e-6, False)
    assert (cfg.n_experts, cfg.topk_experts, cfg.expert_first, cfg.n_held) == (16, 4, 4, 4)


def test_the_published_file_keeps_every_width():
    conf = json.loads((ROOT / "bench/configs" / f"{CONFIG}.json").read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in spec["configs"] if c["name"] == CONFIG)
    assert entry["file"] == f"bench/configs/{CONFIG}.json"
    assert conf["reduced"] == entry["reduced"] == ["num_hidden_layers", "num_experts"]
    assert conf["published"] == {"num_hidden_layers": 94, "num_experts": 128}
    widths = {"hidden_size": 4096, "num_attention_heads": 64, "num_key_value_heads": 4,
              "head_dim": 128, "moe_intermediate_size": 1536, "router_experts": 128,
              "num_experts_per_tok": 8, "vocab_size": 151936, "rms_norm_eps": 1e-6,
              "rope_theta": 1e6}
    assert {k: conf[k] for k in widths} == widths
    cfg = harness.model_config(conf)
    assert (cfg.n_layers, cfg.n_held, cfg.n_experts, cfg.d_ff) == (8, 8, 128, 1536)


def test_the_program_refuses_a_block_it_does_not_run(cell):
    with pytest.raises(ValueError, match="qk_norm"):
        cell.block.arch.model_config(dict(cell.config, qk_norm=False))
    with pytest.raises(ValueError, match="computes"):
        harness.block(dict(cell.config, shared_expert=True), cell.root)


def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 2 and r["failed"] == 0


def test_a_reference_without_qk_norm_is_not_correct(root, cell):
    no_qk = _variant(root, cell, "moe_decoder_no_qk_norm",
                     'F.rope(_norm(q, w["q_norm"], arch), pos',
                     "F.rope(q, pos")
    no_qk = _variant(root, no_qk, "moe_decoder_no_qk_norm",
                     'F.rope(_norm(k, w["k_norm"], arch), pos', "F.rope(k, pos")
    r = _run(no_qk)
    assert not r["correct"], r["checks"]


def test_a_reference_computing_an_expert_held_elsewhere_is_not_correct(root, cell):
    # the held range read one expert on: routes to expert 8, held on
    # another chip, are computed here, and those to expert 4 are not
    shifted = _variant(root, cell, "moe_decoder_shifted",
                       "rel = idx - arch.expert_first", "rel = idx - arch.expert_first - 1")
    r = _run(shifted)
    assert not r["correct"], r["checks"]


def test_prefill_logits_match_the_reference(cell):
    """The whole-prompt prefill (the slab engine's insert path) against the
    reference's logits at the prompt's last row."""
    from repro.models import transformer

    cfg = harness.model_config(cell.config, cell.root)
    bundle = transformer.build(cfg)
    params = weights.make(jax.eval_shape(bundle.init, jax.random.PRNGKey(0)), SEED)
    toks = np.random.default_rng(0).integers(0, 512, size=300).astype(np.int32)
    got, _ = bundle.prefill(params, {"tokens": jnp.asarray(toks)[None],
                                     "lengths": jnp.array([300], jnp.int32)}, capacity=512)
    b = cell.block
    # every layer dense: the prompt's last row is no decode row
    ref = b.reference.logits(cell.config, dict(cell.deployment, skip_layers=2),
                             lambda l: b.arch.layer_view(params, l),
                             b.arch.head_view(params, 512), toks, 299)
    got = np.asarray(got)[0, :512]
    assert got.argmax() == ref[0].argmax()
    np.testing.assert_allclose(got, ref[0], atol=0.15)


def test_work_counts_the_share(cell):
    conf = json.loads((ROOT / "bench/configs" / f"{CONFIG}.json").read_text())
    ef, eb = cell.block.arch.expert_work(conf, 8)
    per_expert = 3 * 4096 * 1536
    # 8 tokens x 8 routes over 128 experts: half a route on the 8 held here
    assert ef == pytest.approx(2 * 8 * 0.5 * per_expert * 8)
    assert eb == pytest.approx(8 * 8 * (1 - (15 / 16) ** 8) * per_expert * 2)
    flops, nbytes = cell.block.arch.weight_work(conf, 8)
    attn = 4096 * (64 + 8) * 128 + 64 * 128 * 4096
    p = 8 * (attn + 4096 * 128) + 151936 * 4096
    assert nbytes == pytest.approx(2 * p + eb)
    assert flops == pytest.approx(2 * 8 * p + ef)



def test_the_benchmark_cell_serves_its_stated_traffic():
    """``qwen3-moe-235b-a22b.longctx_decode_16k``: 8 resident sessions of
    12k-20k tokens on 8 slots of 24576, FIER at a budget of 2048 past the
    stage's first two layers, and a limit on the logit gap."""
    from bench.traffic import resident_sessions

    cell = harness.load_cell("qwen3-moe-235b-a22b.longctx_decode_16k", ROOT)
    assert cell.chips == 1 and cell.config["name"] == CONFIG
    assert cell.deployment == {"slots": 8, "capacity": 24576, "block_size": 32,
                               "budget": 2048, "group": 32, "sink": 4, "recent": 64,
                               "skip_layers": 2, "chunk_tokens": 2048}
    assert (cell.mix["sessions"], cell.mix["checked_sessions"]) == (8, 4)
    lengths = resident_sessions.prompt_lengths(cell.mix["prompt_tokens"], 8)
    assert lengths == [13056, 14336, 15232, 16000, 16768, 17664, 18688, 20480]
    reqs = resident_sessions.generate(cell.mix, cell.config["vocab_size"], 2**31 + 16)
    assert min(r["max_new"] for r in reqs) == 24576 - 20480
    assert set(cell.limits) == {"logit_gap_max"} and 0 < cell.limits["logit_gap_max"] < 1
    # the cell reports the step's device metrics, and no metric whose work
    # is counted from an expectation over random routes
    assert {m["name"] for m in cell.per_layer} == {
        "host_ms_per_step", "decode_step_ms", "retrieve_roofline", "attend_roofline",
        "device_idle_share"}
