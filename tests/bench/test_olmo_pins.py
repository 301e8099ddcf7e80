"""olmo-1b reads what it read before its code moved into
``bench/arch/olmo-1b.py`` and ``bench/reference/fier.py``: the same
weights at a seed, bit for bit, and the same reference logits, at a
width and depth the CPU runs in seconds.  The pinned values were taken
with the harness as it was before the move."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
CONFIG = dict(json.loads((ROOT / "bench/configs/olmo-1b.json").read_text()),
              num_hidden_layers=3, hidden_size=128, num_attention_heads=4,
              num_key_value_heads=4, head_dim=32, intermediate_size=256,
              vocab_size=512)
DEP = {"slots": 2, "capacity": 512, "block_size": 32, "budget": 64, "group": 32,
       "sink": 4, "recent": 16, "skip_layers": 1, "chunk_tokens": 128}
SEED = 2**31 + 11
WEIGHTS_SHA256 = "5003f02295fe8e08f3dbf436a107a951c2de808673e706ff31e6fd624602525b"
LEAVES = ["['embed']", "['layers']['attn']['wk']", "['layers']['attn']['wo']",
          "['layers']['attn']['wq']", "['layers']['attn']['wv']",
          "['layers']['mlp']['w1']", "['layers']['mlp']['w2']", "['layers']['mlp']['w3']"]


@pytest.fixture(scope="module")
def params():
    cell = harness.Cell("tiny", 1, CONFIG, {"deployment": DEP}, {}, [], [])
    return harness.make_params(harness.build_engine(cell), SEED)


def test_weights_at_a_seed(params):
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    assert [jax.tree_util.keystr(p) for p, _ in flat] == LEAVES
    h = hashlib.sha256()
    for _, leaf in flat:
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256


def test_reference_logits_at_a_seed(params):
    block = harness.block(CONFIG)
    toks = np.random.default_rng(7).integers(0, 512, 300).astype(np.int32)
    got = block.reference.logits(
        CONFIG, DEP, lambda l: block.arch.layer_view(params, l),
        block.arch.head_view(params, CONFIG["vocab_size"]), toks, 279)
    want = np.load(ROOT / "tests/bench/data/olmo_tiny_reference_logits.npy")
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
