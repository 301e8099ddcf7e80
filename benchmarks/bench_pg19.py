"""Paper Fig. 5 proxy: LM perplexity vs context length under cache budgets.

PG19's pretrained 7B models aren't available offline, so the *claim shape*
is reproduced on a model trained in-container on the deterministic bigram
corpus: generate continuations scoring next-token NLL with the cache
policy active, for contexts of increasing length; FIER at ~12% budget
should track full-KV closely while Quest (same load ratio) and SLM drift.

Measured as teacher-forced decode: prefill L tokens, then decode the next
32 gold tokens one-by-one through the policy path, accumulating NLL.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import lm_tokens
from repro.launch.compile_cache import enable_compilation_cache

from .common import emit, policy_bundle, train_tiny_lm

EVAL_TOKENS = 32


def nll_for(bundle, params, cfg, toks: jax.Array, prefix: int) -> float:
    B = toks.shape[0]
    pre = {"tokens": toks[:, :prefix],
           "lengths": jnp.full((B,), prefix, jnp.int32)}
    cap = prefix + EVAL_TOKENS
    cap += (-cap) % 8
    logits, cache = jax.jit(lambda p, b: bundle.prefill(p, b, capacity=cap))(params, pre)
    nll, n = 0.0, 0
    decode = jax.jit(bundle.decode_step)
    for t in range(EVAL_TOKENS):
        gold = toks[:, prefix + t]
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll += float(-jnp.take_along_axis(logp, gold[:, None], 1).mean())
        n += 1
        logits, cache = decode(params, gold, cache)
    return nll / n


def run():
    cfg, params = train_tiny_lm("lm")
    params = jax.tree.map(jnp.asarray, params)
    B = 4
    budget = 32  # ~12% of the longest context (matches the paper's 11%)
    toks = lm_tokens(123, 9, B, 384, cfg.vocab)
    for prefix in (64, 128, 256):
        for kind in ("full", "fier", "quest", "slm"):
            bundle = policy_bundle(cfg, kind, budget)
            ppl = float(np.exp(nll_for(bundle, params, cfg, toks, prefix)))
            emit(f"pg19_ppl_{kind}_ctx{prefix}", 0.0,
                 f"ppl={ppl:.3f} budget={budget}")


def main():
    enable_compilation_cache()
    run()


if __name__ == "__main__":
    main()
