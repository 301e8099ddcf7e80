"""bench/work.py against counts worked by hand."""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import pytest

from bench import work

OLMO = work.Shapes(layers=16, d_model=2048, heads=16, kv_heads=16, head_dim=128,
                   d_ff=8192, vocab=50304, budget=1024, group=32, skip_layers=2)
SMALL = work.Shapes(layers=2, d_model=4, heads=2, kv_heads=1, head_dim=2, d_ff=8,
                    vocab=10, budget=4, group=32, skip_layers=1)


def test_parameter_counts():
    # olmo-1b: per layer q,k,v 2048*48*128 + o 16*128*2048 + SwiGLU 3*2048*8192,
    # 16 layers, and the tied head 50304*2048
    assert work.matmul_params(OLMO) == 16 * (12582912 + 4194304 + 50331648) + 103022592
    # 2 layers of (q,k,v 4*4*2 + o 2*2*4 + SwiGLU 3*4*8) + head 10*4
    assert work.matmul_params(SMALL) == 2 * (32 + 16 + 96) + 40


def test_olmo_step_at_8192():
    w = work.step(OLMO, [8192])
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
    one = work.step(SMALL, [3])
    assert one["attend_bytes"] == 1 * (3 * 1 * 2 * 4 + 2 * 2 * 6)
    two = work.step(SMALL, [3, 100])
    assert two["attend_bytes"] == one["attend_bytes"] + (4 * 2 * 4 + 24)
    # ceil: 100 tokens -> 4 groups of 32, 25 code bytes per KV head channel row
    assert two["retrieve_bytes"] - one["retrieve_bytes"] == 25 + 4 * 2 * 2 * 2
