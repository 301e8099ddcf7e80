"""Paper Fig. 7 / Tab. 1 proxy: long-context QA quality across budgets.

LongBench needs pretrained instruction models; the in-container proxy is
multi-needle retrieval QA: several (key → digit-sequence) facts are
scattered through filler, the query names one key, and exact-match
accuracy plays the role of F1.  The paper's ordering should reproduce:
FIER ≥ Quest > SLM at every budget, approaching Full-KV by ~12% budget.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .common import emit, policy_bundle, train_tiny_lm
from repro.launch.compile_cache import enable_compilation_cache

SEQ = 256
N_FACTS = 3
N_DIGITS = 3
KEY0 = 20  # fact-key token ids: KEY0..KEY0+N_FACTS


def make_multi_needle(cfg, B, S, *, seed, step):
    from repro.data.pipeline import lm_tokens

    rng = np.random.default_rng(seed * 7919 + step)
    filler = np.asarray(lm_tokens(seed ^ 0xFAC7, step, B, S, cfg.vocab - 32))
    toks = filler[:, :S] + 32
    answers = rng.integers(0, 10, (B, N_FACTS, N_DIGITS))
    tail = N_DIGITS + 2
    qkey = rng.integers(0, N_FACTS, (B,))
    for b in range(B):
        pos = np.sort(rng.choice(
            np.arange(4, S - tail - (N_DIGITS + 2) * N_FACTS - 2),
            N_FACTS, replace=False,
        ))
        for f in range(N_FACTS):
            p = pos[f] + f * (N_DIGITS + 2)
            toks[b, p] = KEY0 + f
            toks[b, p + 1 : p + 1 + N_DIGITS] = answers[b, f]
        toks[b, S - tail] = 12              # QUERY marker
        toks[b, S - tail + 1] = KEY0 + qkey[b]
        toks[b, S - N_DIGITS:] = answers[b, qkey[b]]
    gold = answers[np.arange(B), qkey]
    mask = np.zeros((B, S), np.float32)
    mask[:, S - N_DIGITS - 1 : S - 1] = 1.0
    batch = {
        "tokens": jnp.asarray(toks, jnp.int32),
        "targets": jnp.asarray(np.concatenate([toks[:, 1:], toks[:, :1]], 1), jnp.int32),
        "loss_mask": jnp.asarray(mask),
    }
    return batch, jnp.asarray(gold, jnp.int32)


def train_needle_model(steps=400):
    import os
    import pickle

    from .common import CACHE_DIR, bench_model_cfg
    from repro.launch.steps import TrainHParams, init_train_state, make_train_step
    from repro.models import build_model

    cfg = bench_model_cfg()
    path = os.path.join(CACHE_DIR, f"params_needle_{steps}.pkl")
    os.makedirs(CACHE_DIR, exist_ok=True)
    bundle = build_model(cfg)
    if os.path.exists(path):
        with open(path, "rb") as f:
            return cfg, pickle.load(f)
    hp = TrainHParams(peak_lr=1e-3, warmup=30, total_steps=steps)
    state = init_train_state(bundle, jax.random.PRNGKey(0), hp)
    step_jit = jax.jit(make_train_step(bundle, hp))
    for s in range(steps):
        batch, _ = make_multi_needle(cfg, 16, SEQ, seed=0, step=s)
        state, metrics = step_jit(state, batch)
        if s % 100 == 0:
            print(f"  [needle] step {s}: loss={float(metrics['loss']):.3f}")
    params = jax.tree.map(np.asarray, state["params"])
    with open(path, "wb") as f:
        pickle.dump(params, f)
    return cfg, params


def accuracy(bundle, params, cfg, n_batches=4) -> float:
    hits = total = 0
    prefill = jax.jit(lambda p, b: bundle.prefill(p, b, capacity=SEQ + 8))
    decode = jax.jit(bundle.decode_step)
    for i in range(n_batches):
        batch, gold = make_multi_needle(cfg, 8, SEQ, seed=321, step=i)
        prompt = batch["tokens"][:, : SEQ - N_DIGITS]
        B = prompt.shape[0]
        pre = {"tokens": prompt, "lengths": jnp.full((B,), prompt.shape[1], jnp.int32)}
        logits, cache = prefill(params, pre)
        digs = []
        for _ in range(N_DIGITS):
            tok = jnp.argmax(logits[:, :10], axis=-1).astype(jnp.int32)
            digs.append(tok)
            logits, cache = decode(params, tok, cache)
        got = np.stack([np.asarray(d) for d in digs], 1)
        hits += int((got == np.asarray(gold)).all(1).sum())
        total += B
    return hits / total


def run():
    cfg, params = train_needle_model()
    params = jax.tree.map(jnp.asarray, params)
    for budget in (16, 32, 64):
        for kind in ("full", "fier", "quest", "slm"):
            bundle = policy_bundle(cfg, kind, budget)
            acc = accuracy(bundle, params, cfg)
            emit(f"longbench_proxy_{kind}_b{budget}", 0.0,
                 f"acc={acc:.2f} ctx={SEQ} facts={N_FACTS}")


def main():
    enable_compilation_cache()
    run()


if __name__ == "__main__":
    main()
