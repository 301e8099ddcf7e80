"""QK-norm (Qwen3): a per-head RMSNorm with gains on q and k, applied
before RoPE, so the cache and FIER's side-car hold the normed keys.  Off
(every other configuration), the program is the one it was before."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models.layers import apply_rope
from repro.serving import Engine
from repro.serving.engine import serving_policy

QWEN = dataclasses.replace(get_config("qwen3-moe-235b-a22b"), n_layers=2, d_model=128,
                           n_heads=4, n_kv_heads=2, d_head=32, d_ff=64, vocab=256,
                           n_experts=8, topk_experts=2)


def _rms(x, g, eps):
    x = np.asarray(x, np.float64)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * np.asarray(g)


@pytest.mark.parametrize("qk_norm", [True, False])
def test_qkv_proj_norms_each_head_before_rope(qk_norm):
    cfg = dataclasses.replace(QWEN, qk_norm=qk_norm)
    p = attn.init_attention(jax.random.PRNGKey(0), cfg)
    assert ("q_norm" in p) == ("k_norm" in p) == qk_norm
    if qk_norm:
        k = jax.random.split(jax.random.PRNGKey(1), 2)
        p = dict(p, q_norm=1 + 0.1 * jax.random.normal(k[0], (32,)),
                 k_norm=1 + 0.1 * jax.random.normal(k[1], (32,)))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 5, 128), jnp.float32)
    pos = jnp.arange(5)[None] + 7
    q, k, v = attn.qkv_proj(p, x, cfg, positions=pos)
    q0 = (x @ p["wq"]).reshape(1, 5, 4, 32)
    k0 = (x @ p["wk"]).reshape(1, 5, 2, 32)
    if qk_norm:
        q0 = _rms(q0, p["q_norm"], cfg.norm_eps)
        k0 = _rms(k0, p["k_norm"], cfg.norm_eps)
    want_q = apply_rope(jnp.asarray(q0, jnp.float32), pos, cfg.rope_theta)
    want_k = apply_rope(jnp.asarray(k0, jnp.float32), pos, cfg.rope_theta)
    np.testing.assert_allclose(np.asarray(q), np.asarray(want_q), atol=1e-5)
    np.testing.assert_allclose(np.asarray(k), np.asarray(want_k), atol=1e-5)
    np.testing.assert_allclose(np.asarray(v), np.asarray(x @ p["wv"]).reshape(1, 5, 2, 32),
                               atol=1e-5)


def test_qwen3_configuration_states_the_published_block():
    cfg = get_config("qwen3-moe-235b-a22b")
    assert (cfg.qk_norm, cfg.norm_eps, cfg.tie_embeddings, cfg.n_held) == (True, 1e-6,
                                                                          False, 128)
    assert "Qwen3-235B-A22B" in cfg.source


# SHA-256 of the lowered paged decode step and final prefill chunk of a
# small olmo-1b, taken from the program before QK-norm, the norm epsilon
# and the expert share existed: with them off, the program is unchanged.
# The decode pin was taken again when the select-and-attend gather went a
# block deep; that program differs from the one before only inside
# paged_fused_sparse_attention_hm and in the numbering of helper functions
OLMO_PROGRAMS = {
    "decode": "70a2bbdf3b3d2417ad42a983bc5241f610d5b19ab17f5ffea16dd2d045e72754",
    "chunk": "40684d65dba24ebaf307d4eff4bee0c48884e9c1130a3e651c4cce776e13f9fa",
}


def test_without_qk_norm_the_olmo_program_is_unchanged():
    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=3, d_model=256, n_heads=4,
                              n_kv_heads=4, d_head=64, d_ff=512, vocab=1024)
    pol = dataclasses.replace(
        serving_policy(budget=64, group=32, skip_layers=1, sink=4, recent=16,
                       pipeline="one_pass", layout="paged"), block_size=32)
    eng = Engine.build(cfg, n_slots=2, capacity=512, policy=pol, layout="paged",
                       block_size=32)
    params = jax.eval_shape(eng.bundle.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: eng.bundle.init_cache(2, 512, 0))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    batch = {"tokens": i32(1, 128), "start": i32(), "slot": i32(), "total": i32(),
             "table_row": i32(16)}
    texts = {
        "decode": eng._decode_active.lower(params, i32(2), cache,
                                           jax.ShapeDtypeStruct((2,), jnp.bool_)),
        "chunk": eng._chunk_fn(True).lower(params, batch, cache),
    }
    got = {k: hashlib.sha256(t.as_text().encode()).hexdigest() for k, t in texts.items()}
    assert got == OLMO_PROGRAMS
