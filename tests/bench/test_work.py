"""bench/work.py, with olmo-1b's weights counted by its architecture
module, against counts worked by hand."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import json
from pathlib import Path

import pytest

from bench import harness, work

ROOT = Path(__file__).resolve().parents[2]
OLMO_CONFIG = json.loads((ROOT / "bench/configs/olmo-1b.json").read_text())
OLMO_ARCH = harness.block(OLMO_CONFIG).arch
OLMO = work.Shapes(layers=16, d_model=2048, heads=16, kv_heads=16, head_dim=128,
                   budget=1024, group=32, skip_layers=2)
SMALL_CONFIG = dict(OLMO_CONFIG, num_hidden_layers=2, hidden_size=4, num_attention_heads=2,
                    num_key_value_heads=1, head_dim=2, intermediate_size=8, vocab_size=10)
SMALL = work.Shapes(layers=2, d_model=4, heads=2, kv_heads=1, head_dim=2, budget=4,
                    group=32, skip_layers=1)


def _step(shapes, config, lengths):
    return work.step(shapes, lengths, OLMO_ARCH.weight_work(config, len(lengths)))


def test_parameter_counts():
    # olmo-1b: per layer q,k,v 2048*48*128 + o 16*128*2048 + SwiGLU 3*2048*8192,
    # 16 layers, and the tied head 50304*2048
    assert OLMO_ARCH.matmul_params(OLMO_CONFIG) == (
        16 * (12582912 + 4194304 + 50331648) + 103022592)
    # 2 layers of (q,k,v 4*4*2 + o 2*2*4 + SwiGLU 3*4*8) + head 10*4
    assert OLMO_ARCH.matmul_params(SMALL_CONFIG) == 2 * (32 + 16 + 96) + 40


def test_cell_shapes():
    cell = harness.load_cell("olmo-1b.longctx_decode", ROOT)
    assert work.Shapes.of(cell.config, cell.deployment) == OLMO


def test_olmo_step_at_8192():
    w = _step(OLMO, OLMO_CONFIG, [8192])
    # retrieval, 14 FIER layers: codes 8192*16*128/8 + scale/zero 256*16*128*2*2
    assert w["retrieve_bytes"] == 14 * (2097152 + 2097152)
    assert w["retrieve_flops"] == 14 * 2 * 16 * 8192 * 128
    # attend: 1024 K and V rows per KV head in bf16, bf16 q in, f32 out
    assert w["attend_bytes"] == 14 * (1024 * 16 * 128 * 4 + 16 * 128 * 6)
    assert w["attend_flops"] == 14 * 4 * 16 * 1024 * 128
    # weights in bf16 + embedding row + K/V append + 2 dense layers + FIER
    assert w["bytes"] == (2353528832 + 4096 + 131072 + 134234112
                          + 58720256 + 117612544)
    assert w["flops"] == 2353528832 + 134217728 + 469762048 + 117440512
    peak = {"bf16_flop_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert work.roofline_s(w["flops"], w["bytes"], peak) == pytest.approx(
        2664230912 / 819e9)


def test_budget_caps_attend_and_slots_add_up():
    one = _step(SMALL, SMALL_CONFIG, [3])
    assert one["attend_bytes"] == 1 * (3 * 1 * 2 * 4 + 2 * 2 * 6)
    two = _step(SMALL, SMALL_CONFIG, [3, 100])
    assert two["attend_bytes"] == one["attend_bytes"] + (4 * 2 * 4 + 24)
    # ceil: 100 tokens -> 4 groups of 32, 25 code bytes per KV head channel row
    assert two["retrieve_bytes"] - one["retrieve_bytes"] == 25 + 4 * 2 * 2 * 2


def test_cell_step_work_is_unchanged():
    """Every count of the cell's steps, as the per-layer readers read them."""
    cell = harness.load_cell("olmo-1b.longctx_decode", ROOT)
    shapes = work.Shapes.of(cell.config, cell.deployment)
    for lengths, want in STEP_WORK:
        got = _step(shapes, cell.config, lengths)
        assert got == want, lengths


# the cell's counts as bench/work.py gave them while it still counted OLMo's
# weights itself, at the cell's prompt lengths and 100 tokens later
STEP_WORK = [
    ([7552, 8320, 9088, 9984],
     {"retrieve_flops": 2003828736.0, "retrieve_bytes": 250478592.0,
      "attend_flops": 469762048.0, "attend_bytes": 470450176.0,
      "flops": 12460228608.0, "bytes": 3647586304.0}),
    ([7652, 8420, 9188, 10084],
     {"retrieve_flops": 2026766336.0, "retrieve_bytes": 253747200.0,
      "attend_flops": 469762048.0, "attend_bytes": 470450176.0,
      "flops": 12489719808.0, "bytes": 3657408512.0}),
]
