"""Serving engine + continuous-batching scheduler."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.policy import PolicyConfig
from repro.models import build_model
from repro.serving import ContinuousScheduler, Engine, Request, SamplingConfig
from repro.serving.engine import sample_token


@pytest.fixture(scope="module")
def setup():
    cfg = reduced_config("olmo-1b")
    pol = PolicyConfig(kind="fier", budget=16, group=8, skip_layers=1)
    bundle = build_model(cfg, pol)
    params = bundle.init(jax.random.PRNGKey(0))
    return cfg, bundle, params


def test_continuous_matches_static(setup):
    cfg, bundle, params = setup
    eng = Engine(bundle, n_slots=3, capacity=64)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    reqs = [Request(rid=i, tokens=list(range(3 + i, 11 + i)), max_new=5)
            for i in range(5)]
    out = sched.run(reqs)
    assert all(len(v) == 5 for v in out.values())
    assert sched.mean_occupancy > 1.5  # slots actually shared

    eng1 = Engine(bundle, n_slots=1, capacity=64)
    for r in reqs[:2]:
        p = jnp.asarray(np.asarray(r.tokens, np.int32)[None])
        toks = eng1.generate(params, p, jnp.array([len(r.tokens)], jnp.int32), 5)
        assert np.asarray(toks[0]).tolist() == out[r.rid], r.rid


def test_eos_terminates_early(setup):
    cfg, bundle, params = setup
    eng = Engine(bundle, n_slots=2, capacity=64)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    # find what the model emits first, then use it as the EOS token
    probe = ContinuousScheduler(Engine(bundle, n_slots=1, capacity=64), params,
                                pad_prompt_to=16)
    first = probe.run([Request(rid=0, tokens=[1, 2, 3], max_new=2)])[0][0]
    reqs = [Request(rid=0, tokens=[1, 2, 3], max_new=50, eos=first)]
    out = sched.run(reqs)
    assert len(out[0]) == 1  # stopped at eos immediately


def test_sampling_modes():
    logits = jnp.array([[0.0, 5.0, 1.0, -2.0]])
    greedy = sample_token(jax.random.PRNGKey(0), logits, SamplingConfig())
    assert int(greedy[0]) == 1
    topk = sample_token(
        jax.random.PRNGKey(0), logits, SamplingConfig(temperature=1.0, top_k=2)
    )
    assert int(topk[0]) in (1, 2)


def test_scheduler_threads_fresh_rng_each_step(setup, monkeypatch):
    """Regression: Engine.decode used to fall back to PRNGKey(0) on every
    call and the scheduler never passed an rng, so temperature > 0 serving
    resampled from the identical key each step.  Two consecutive sampled
    steps must now use distinct keys."""
    import repro.serving.engine as engine_mod

    cfg, bundle, params = setup
    seen = []
    orig = engine_mod.sample_token

    def spy(rng, logits, scfg):
        seen.append(np.asarray(rng).copy())
        return orig(rng, logits, scfg)

    monkeypatch.setattr(engine_mod, "sample_token", spy)
    eng = Engine(bundle, n_slots=2, capacity=64,
                 sampling=SamplingConfig(temperature=1.0, top_k=2))
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    sched.run([Request(rid=0, tokens=[1, 2, 3], max_new=6)])
    assert len(seen) >= 2
    keys = {tuple(k.tolist()) for k in seen}
    assert len(keys) == len(seen), "sampling rng key reused across steps"


def test_engine_decode_fallback_rng_advances(setup):
    """Engine.decode without an explicit rng must split a fresh key per
    call (not PRNGKey(0) forever): consecutive sampled steps differ."""
    cfg, bundle, params = setup
    eng = Engine(bundle, n_slots=1, capacity=64,
                 sampling=SamplingConfig(temperature=1.0, top_k=0))
    k0 = eng._rng
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "lengths": jnp.array([4], jnp.int32)}
    _, cache = eng.prefill_batch(params, batch)
    tok = jnp.zeros((1,), jnp.int32)
    draws = []
    for _ in range(8):
        tok, _, cache = eng.decode(params, tok, cache)
        draws.append(int(tok[0]))
    assert not np.array_equal(np.asarray(eng._rng), np.asarray(k0))
    # 8 draws at temperature 1.0 over a 512-vocab softmax: all-identical
    # only if the rng key repeats (the exact bug) or the distribution is
    # near-deterministic — the trained-free random init it isn't
    assert len(set(draws)) > 1, draws


def test_scheduler_queue_fifo_order(setup):
    """The deque-backed admission queue must preserve FIFO order: with one
    slot, requests finish in submission order."""
    cfg, bundle, params = setup
    eng = Engine(bundle, n_slots=1, capacity=64)
    sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
    reqs = [Request(rid=i, tokens=[3 + i, 4 + i], max_new=2) for i in range(4)]
    admits = []
    orig_admit = sched._admit

    def tracking_admit(queue, cache, cur):
        before = [r.rid for r in queue]
        res = orig_admit(queue, cache, cur)
        admits.append((before, [r.rid for r in queue]))
        return res

    sched._admit = tracking_admit
    out = sched.run(reqs)
    assert set(out) == {0, 1, 2, 3}
    # every admission must take from the *head*: the remaining queue is a
    # suffix of the pre-admit queue (tail-popping LIFO would leave a
    # prefix instead and fail here)
    for before, after in admits:
        assert after == before[len(before) - len(after):], (before, after)


def test_slot_isolation(setup):
    """A request's output must not depend on what occupies other slots."""
    cfg, bundle, params = setup
    out = {}
    for other in ([11, 12, 13, 14], [99, 98, 97]):
        eng = Engine(bundle, n_slots=2, capacity=64)
        sched = ContinuousScheduler(eng, params, pad_prompt_to=16)
        reqs = [
            Request(rid=0, tokens=[5, 6, 7, 8], max_new=4),
            Request(rid=1, tokens=other, max_new=4),
        ]
        out[tuple(other)] = sched.run(reqs)[0]
    vals = list(out.values())
    assert vals[0] == vals[1], "slot contents leaked across requests"


# ---------------------------------------------------------- chunked prefill

@pytest.fixture(scope="module")
def chunk_setup():
    cfg = reduced_config("olmo-1b")

    def mk(layout, pool_blocks=0):
        pol = PolicyConfig(
            kind="fier", budget=16, group=8, skip_layers=1,
            pipeline="one_pass", layout=layout,
            block_size=8, pool_blocks=pool_blocks,
        )
        return build_model(cfg, pol)

    slab = mk("slab")
    params = slab.init(jax.random.PRNGKey(0))
    return cfg, mk, slab, params


def test_chunked_prefill_matches_monolithic(chunk_setup):
    """Chunked admission must be a pure scheduling change: token-for-token
    identical outputs to monolithic prefill, on both cache layouts."""
    cfg, mk, slab, params = chunk_setup

    def reqs():
        return [
            Request(rid=i, tokens=list(range(3 + i, 20 + 3 * i)), max_new=6)
            for i in range(4)
        ]

    for bundle in (slab, mk("paged", pool_blocks=40)):
        mono = ContinuousScheduler(
            Engine(bundle, n_slots=2, capacity=64), params
        ).run(reqs())
        chunked = ContinuousScheduler(
            Engine(bundle, n_slots=2, capacity=64), params, chunk_tokens=5
        ).run(reqs())
        assert chunked == mono, bundle.policy.layout


def test_decode_runs_between_chunks(chunk_setup):
    """The token quantum interleaves: while a long prompt is admitted
    chunk by chunk, the resident request keeps decoding in between."""
    cfg, mk, slab, params = chunk_setup
    eng = Engine(slab, n_slots=2, capacity=64)
    sched = ContinuousScheduler(eng, params, chunk_tokens=4)
    events = []
    orig_chunk, orig_decode = eng.prefill_chunk, eng.decode

    def chunk_spy(*a, **k):
        events.append("chunk")
        return orig_chunk(*a, **k)

    def decode_spy(*a, **k):
        events.append("decode")
        return orig_decode(*a, **k)

    eng.prefill_chunk, eng.decode = chunk_spy, decode_spy
    sched.start()
    short = Request(rid=0, tokens=[2, 3, 4], max_new=30)
    sched.submit(short)
    sched.step()  # short admitted (single chunk) and decoding
    sched.submit(Request(rid=1, tokens=list(range(2, 22)), max_new=2))
    while sched.busy:
        sched.step()
    assert len(short.out) == 30
    ci = [i for i, e in enumerate(events) if e == "chunk"]
    assert len(ci) >= 3  # short's single chunk + the long prompt's 5
    assert any(
        "decode" in events[a + 1:b] for a, b in zip(ci[1:], ci[2:])
    ), events


def test_chunked_preemption_resumes_from_boundary(chunk_setup):
    """A half-prefilled request that hits a dry pool aborts itself,
    re-queues at the head, resumes from its completed-chunk boundary (not
    token 0), and still produces the un-contended reference output."""
    cfg, mk, slab, params = chunk_setup

    def reqs():
        return [
            Request(rid=0, tokens=list(range(2, 42)), max_new=8),
            Request(rid=1, tokens=list(range(5, 53)), max_new=4),
        ]

    ref = ContinuousScheduler(
        Engine(mk("paged", pool_blocks=32), n_slots=2, capacity=64), params
    ).run(reqs())

    eng = Engine(mk("paged", pool_blocks=9), n_slots=2, capacity=64)
    sched = ContinuousScheduler(eng, params, chunk_tokens=16)
    calls, aborts = [], []
    orig_chunk, orig_abort = eng.prefill_chunk, eng.abort_chunked

    def chunk_spy(p, c, slot, toks, start, n):
        calls.append((sched._prefilling.req.rid, int(start)))
        return orig_chunk(p, c, slot, toks, start, n)

    def abort_spy(cache, slot):
        aborts.append((sched._prefilling.req.rid, len(calls)))
        return orig_abort(cache, slot)

    eng.prefill_chunk, eng.abort_chunked = chunk_spy, abort_spy
    out = sched.run(reqs())
    assert out == ref
    assert sched.prefill_aborts >= 1
    resumed = False
    for rid, idx in aborts:
        nxt = next((s for r, s in calls[idx:] if r == rid), None)
        resumed |= nxt is not None and nxt > 0
    assert resumed, (calls, aborts)


def test_paged_admission_skips_blocked_head(chunk_setup):
    """Head-of-line fix: a big request that can't get blocks yet must not
    block a later small request when a slot and blocks are free."""
    cfg, mk, slab, params = chunk_setup
    eng = Engine(mk("paged", pool_blocks=9), n_slots=2, capacity=64)
    sched = ContinuousScheduler(eng, params)  # monolithic admission
    sched.start()
    hold = Request(rid=0, tokens=list(range(2, 26)), max_new=20)
    sched.submit(hold)
    sched.step()
    assert hold in sched.running.values()  # 3 of 8 usable blocks held
    big = Request(rid=1, tokens=list(range(3, 50)), max_new=4)    # 6 blocks
    small = Request(rid=2, tokens=list(range(4, 12)), max_new=4)  # 1 block
    sched.submit(big)
    sched.submit(small)
    sched.step()
    assert small in sched.running.values() or small.done
    assert not big.out and not big.done  # still queued, not blocking
    while sched.busy:
        sched.step()
    assert len(big.out) == 4 and len(small.out) == 4 and len(hold.out) == 20


def test_only_the_step_s_own_cache_leaves_lack_a_batch_axis():
    """An MoE step's route counts belong to no slot; any other cache leaf
    without a batch axis is a mistake the engine refuses."""
    from types import SimpleNamespace

    from repro.serving.engine import _cache_batch_axes

    def init_cache(B, capacity, length):
        return {"k": jnp.zeros((2, B, capacity)), "length": jnp.zeros((B,), jnp.int32),
                "moe_counts": jnp.zeros((2,), jnp.int32)}

    axes = _cache_batch_axes(SimpleNamespace(init_cache=init_cache), 16)
    assert axes == {"k": 1, "length": 0, "moe_counts": -1}

    def unbatched(B, capacity, length):
        return dict(init_cache(B, capacity, length), scale=jnp.zeros((4,)))

    with pytest.raises(ValueError, match="ambiguous batch axis"):
        _cache_batch_axes(SimpleNamespace(init_cache=unbatched), 16)
