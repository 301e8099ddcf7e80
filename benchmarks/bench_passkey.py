"""Paper Tab. 2: passkey retrieval accuracy under cache budgets.

A tiny model is trained in-container on the passkey task (hidden 5-digit
key + filler + query), then evaluated with each policy at budgets that are
small fractions of the context.  The paper's structural claim reproduces:
eviction (SLM) collapses — the passkey tokens are outside sink+recent —
while retrieval (FIER/Quest) recovers them, FIER at finer granularity.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.passkey import N_DIGITS, make_passkey_batch
from repro.launch.compile_cache import enable_compilation_cache

from .common import emit, policy_bundle, train_tiny_lm

SEQ = 256


def accuracy(bundle, params, cfg, n_batches: int = 4, depth=None) -> float:
    hits, total = 0, 0
    prefill = jax.jit(lambda p, b: bundle.prefill(p, b, capacity=SEQ + 8))
    decode = jax.jit(bundle.decode_step)
    for i in range(n_batches):
        batch, answers = make_passkey_batch(cfg, 8, SEQ, seed=999, step=i,
                                            depth=depth)
        prompt = batch["tokens"][:, : SEQ - N_DIGITS]
        B = prompt.shape[0]
        pre = {"tokens": prompt, "lengths": jnp.full((B,), prompt.shape[1], jnp.int32)}
        logits, cache = prefill(params, pre)
        digs = []
        for _ in range(N_DIGITS):
            tok = jnp.argmax(logits[:, :10], axis=-1).astype(jnp.int32)  # digit head
            digs.append(tok)
            logits, cache = decode(params, tok, cache)
        got = np.stack([np.asarray(d) for d in digs], 1)
        hits += int((got == np.asarray(answers)).all(axis=1).sum())
        total += B
    return hits / total


def run():
    cfg, params = train_tiny_lm("passkey", steps=600)
    params = jax.tree.map(jnp.asarray, params)
    for budget in (16, 32, 64):
        for kind in ("full", "fier", "quest", "slm"):
            bundle = policy_bundle(cfg, kind, budget)
            acc = accuracy(bundle, params, cfg)
            emit(f"passkey_{kind}_b{budget}", 0.0, f"acc={acc:.2f} ctx={SEQ}")


def main():
    enable_compilation_cache()
    run()


if __name__ == "__main__":
    main()
