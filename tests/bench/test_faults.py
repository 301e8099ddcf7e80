"""A whole run of the harness at a size the CPU holds, with the timed
path sound and with it broken underneath: ``correct`` must come out true
for the sound run and false for each fault a served cell can have, and
the float8 control, put in the program's place, must read as not
correct.

The run skips ``bench/run.py``'s look for a chip and calls the harness
directly; the Pallas kernels run in interpret mode.
"""
import _bench_root  # noqa: F401  (repo root and src/ on sys.path)
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
SEED = 2**31 + 11
# olmo-1b's block at a width and depth the CPU runs in seconds
CONFIG = dict(json.loads((ROOT / "bench/configs/olmo-1b.json").read_text()),
              num_hidden_layers=3, hidden_size=128, num_attention_heads=4,
              num_key_value_heads=4, head_dim=32, intermediate_size=256,
              vocab_size=512)
MIX = {"kind": "resident_sessions",
       "deployment": {"slots": 2, "capacity": 512, "block_size": 32, "budget": 64,
                      "group": 32, "sink": 4, "recent": 16, "skip_layers": 1,
                      "chunk_tokens": 128},
       "sessions": 2,
       "prompt_tokens": {"lognormal_median": 250, "lognormal_sigma": 0.3,
                         "min": 128, "max": 384, "round_to": 32},
       "max_new": "capacity_minus_prompt", "checked_sessions": 2}
# at this size sound runs read up to ~0.25 (FIER's 1-bit selection seen
# through bf16 keys) and a wrong token reads the width of the logit spread
LIMIT = 0.5


def _cell():
    spec = harness.load_spec(ROOT)
    return harness.Cell("tiny", 1, CONFIG, MIX, {"logit_gap_max": LIMIT},
                        spec["end_to_end"], [])


def _run(fault=None, control=False):
    return harness.run(_cell(), SEED, 1.0, False, t0=time.perf_counter(),
                       root=ROOT, fault=fault, control=control)


def _wrap_decode(eng, body):
    real = eng.decode
    eng.decode = lambda params, tokens, cache, active=None, rng=None: body(
        real, params, tokens, cache, active, rng)


def state_unchanged(eng):
    """Each decode step hands back the cache it was given."""
    def body(real, params, tokens, cache, active, rng):
        keep = jax.tree.map(jnp.copy, cache)
        nxt, logits, _ = real(params, tokens, cache, active=active, rng=rng)
        return nxt, logits, keep
    _wrap_decode(eng, body)


def half_batch(eng):
    """The second half of the slots is left out of every decode step; its
    sessions are served their input token again."""
    def body(real, params, tokens, cache, active, rng):
        keep = jnp.arange(tokens.shape[0]) < tokens.shape[0] // 2
        act = keep if active is None else active & keep
        nxt, logits, cache = real(params, tokens, cache, active=act, rng=rng)
        return jnp.where(keep, nxt, tokens), logits, cache
    _wrap_decode(eng, body)


def token_altered(eng):
    """One token, of slot 0 at the fifth decode step, is altered where the
    step produces it."""
    calls = []

    def body(real, params, tokens, cache, active, rng):
        nxt, logits, cache = real(params, tokens, cache, active=active, rng=rng)
        calls.append(1)
        if len(calls) == 5:
            nxt = nxt.at[0].set((nxt[0] + 1) % CONFIG["vocab_size"])
        return nxt, logits, cache
    _wrap_decode(eng, body)


def test_sound_run_is_correct():
    r = _run()
    assert r["correct"], r["checks"]
    assert r["attempted"] == 2 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"decode_tokens_per_s", "itl_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", [state_unchanged, half_batch, token_altered],
                         ids=lambda f: f.__name__)
def test_fault_is_not_correct(fault):
    r = _run(fault)
    assert not r["correct"], r["checks"]


def test_control_reads_above_the_limit():
    r = _run(control=True)
    assert r["checks"]["logit_gap_max"]["value"] > LIMIT
    assert not r["correct"]
