"""Paper Tab. 3 ablation: token granularity vs quantized attention.

Grid over the selection mechanisms at matched/varied load ratios:
  Quest p∈{8,16,32} (box bounds), Quest-p16-w/quant (page scores from the
  mean 1-bit token score — the paper's hybrid row), FIER g∈{8,32,64}.
Metric: top-k recall against full-precision attention on trained-model
keys + passkey accuracy for the main pairing.  Load ratios printed beside
each row (Eqs. 4/8).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quantize as qz, quest, retrieval as rt
from repro.launch.compile_cache import enable_compilation_cache

from .common import emit, train_tiny_lm
from .bench_recall import _recall, model_keys


def run():
    q, K = model_keys(S=256)
    S, Hq = K.shape[1], q.shape[1]
    exact = np.asarray(rt.exact_scores(q, K))
    kk = 32

    for g in (8, 32, 64):
        s = np.asarray(rt.approx_scores(q, qz.quantize(K, g)))
        sel = np.argsort(-s, axis=-1)[..., :kk]
        emit(f"ablation_fier_g{g}", 0.0,
             f"recall@{kk}={_recall(sel, exact, kk):.3f} "
             f"load_ratio={qz.load_ratio(g):.4f}")

    for p in (8, 16, 32):
        meta = quest.build_page_meta(K, p)
        ps = np.asarray(quest.page_scores(q, meta))
        sel = []
        for b in range(ps.shape[0]):
            row = []
            for h in range(Hq):
                pages = np.argsort(-ps[b, h])[: max(kk // p, 1)]
                ids = np.concatenate([np.arange(x * p, (x + 1) * p) for x in pages])
                row.append(ids[:kk] if len(ids) >= kk
                           else np.pad(ids, (0, kk - len(ids))))
            sel.append(row)
        emit(f"ablation_quest_p{p}", 0.0,
             f"recall@{kk}={_recall(np.asarray(sel), exact, kk):.3f} "
             f"load_ratio={2 / p:.4f}")

    # Quest-p16-w/quant: page scores from mean 1-bit token scores
    qk = qz.quantize(K, 32)
    ps = np.asarray(quest.quant_page_scores(q, qk, 16))
    sel = []
    for b in range(ps.shape[0]):
        row = []
        for h in range(Hq):
            pages = np.argsort(-ps[b, h])[: max(kk // 16, 1)]
            ids = np.concatenate([np.arange(x * 16, (x + 1) * 16) for x in pages])
            row.append(ids[:kk])
        sel.append(row)
    emit("ablation_quest_p16_wquant", 0.0,
         f"recall@{kk}={_recall(np.asarray(sel), exact, kk):.3f} load_ratio=0.1250")


def main():
    enable_compilation_cache()
    run()


if __name__ == "__main__":
    main()
