"""Paper Figs. 3 & 6: top-k recall of 1-bit scores vs page-level selection.

Measures, on (a) synthetic outlier-channel keys and (b) keys produced by a
*trained* tiny LM mid-prefill, the overlap between the policy's selected
tokens and the full-precision attention top-k — the paper's core
mechanism claim: token-level 1-bit ≫ page-level min/max at equal load
ratio, and ≈ full-precision selection.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize as qz
from repro.core import quest, retrieval as rt
from repro.launch.compile_cache import enable_compilation_cache

from .common import emit, timeit, train_tiny_lm


def _recall(selected_idx: np.ndarray, exact_scores: np.ndarray, k: int) -> float:
    """selected_idx [B,H,k'], exact [B,H,S]."""
    top = np.argsort(-exact_scores, axis=-1)[..., :k]
    out = []
    for b in range(top.shape[0]):
        for h in range(top.shape[1]):
            out.append(len(set(top[b, h]) & set(selected_idx[b, h])) / k)
    return float(np.mean(out))


def synthetic_keys(seed=0, B=2, S=2048, Hkv=2, Hq=4, D=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    chan = jnp.exp(jax.random.normal(ks[2], (D,)))
    K = jax.random.normal(ks[0], (B, S, Hkv, D)) * chan
    q = jax.random.normal(ks[1], (B, Hq, D)) * chan
    return q, K


def model_keys(S=256):
    """Keys/query from a trained model's first policy layer at prefill."""
    cfg, params = train_tiny_lm("lm")
    from repro.data.pipeline import make_prefill_batch
    from repro.models import attention as attn
    from repro.models.layers import apply_norm

    batch = make_prefill_batch(cfg, 2, S)
    emb = jnp.take(jnp.asarray(params["embed"]), batch["tokens"], axis=0)
    lp = jax.tree.map(lambda a: jnp.asarray(a)[2], params["layers"])  # layer 2
    xn = apply_norm(emb.astype(jnp.bfloat16), lp["norm1"], cfg.norm)
    q_all, K, _ = attn.qkv_proj(lp["attn"], xn, cfg, positions=None)
    return q_all[:, -1].astype(jnp.float32), K.astype(jnp.float32)


def run(budget_k: int = 64) -> list[str]:
    rows = []
    for src, (q, K) in (("synthetic", synthetic_keys()), ("trained", model_keys())):
        S = K.shape[1]
        Hkv, Hq = K.shape[2], q.shape[1]
        exact = np.asarray(rt.exact_scores(q, K))
        kk = min(budget_k, S // 4)

        for g in (32, 128):
            if S % g:
                continue
            t0 = timeit(lambda: rt.approx_scores(q, qz.quantize(K, g)))
            s = np.asarray(rt.approx_scores(q, qz.quantize(K, g)))
            sel = np.argsort(-s, axis=-1)[..., :kk]
            r = _recall(sel, exact, kk)
            emit(f"recall_fier_g{g}_{src}", t0, f"recall@{kk}={r:.3f}")
            rows.append(r)

        for p in (16, 32):
            if S % p:
                continue
            meta = quest.build_page_meta(K, p)
            ps = np.asarray(quest.page_scores(q, meta))
            sel = []
            for b in range(ps.shape[0]):
                row = []
                for h in range(ps.shape[1]):
                    pages = np.argsort(-ps[b, h])[: max(kk // p, 1)]
                    ids = np.concatenate([np.arange(x * p, (x + 1) * p) for x in pages])
                    row.append(ids[:kk] if len(ids) >= kk else
                               np.pad(ids, (0, kk - len(ids))))
                sel.append(row)
            r = _recall(np.asarray(sel), exact, kk)
            t0 = timeit(lambda: quest.page_scores(q, meta))
            emit(f"recall_quest_p{p}_{src}", t0, f"recall@{kk}={r:.3f}")
            rows.append(r)

        # random-page floor
        rng = np.random.default_rng(0)
        sel = np.stack([
            np.stack([rng.choice(S, kk, replace=False) for _ in range(Hq)])
            for _ in range(K.shape[0])
        ])
        emit(f"recall_random_{src}", 0.0,
             f"recall@{kk}={_recall(sel, exact, kk):.3f}")
    return rows


def main():
    enable_compilation_cache()
    run()


if __name__ == "__main__":
    main()
