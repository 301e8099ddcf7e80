"""Serving driver: ``python -m repro.launch.serve --arch olmo-1b --reduced``

Spins up the Engine + continuous-batching scheduler on synthetic requests
and reports throughput/occupancy.  Policy selectable: full | fier | quest.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.policy import PolicyConfig
from repro.data.pipeline import lm_tokens
from repro.launch.compile_cache import enable_compilation_cache
from repro.launch.mesh import batch_axes, make_local_mesh
from repro.models import DistConfig, build_model
from repro.serving import ContinuousScheduler, Engine, Request, SamplingConfig


def main() -> None:
    enable_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--policy", default="fier", choices=["full", "fier", "quest"])
    ap.add_argument("--budget", type=int, default=64)
    ap.add_argument("--group", type=int, default=8)
    ap.add_argument("--n-requests", type=int, default=12)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=128)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="block-pool KV cache (prefix sharing + preemption)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="pool size in blocks; 0 = worst-case default")
    args = ap.parse_args()

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    mesh = make_local_mesh(model_axis=args.model_axis)
    layout = "paged" if args.paged else "slab"
    pol = None
    if args.policy != "full" and not cfg.attention_free:
        # both layouts serve through the one-pass kernel pipeline; the jnp
        # reference pipeline is the tests' oracle, not a serving path
        pol = PolicyConfig(
            kind=args.policy, budget=args.budget, group=args.group,
            skip_layers=1 if args.reduced else 2,
            pipeline="one_pass" if args.policy == "fier" else "reference",
            layout=layout,
            block_size=args.block_size, pool_blocks=args.pool_blocks,
        )
    elif args.paged:
        pol = PolicyConfig(
            kind="full", layout="paged", block_size=args.block_size,
            pool_blocks=args.pool_blocks,
        )
    dcfg = DistConfig(mesh=mesh, batch_axes=batch_axes(mesh))
    if args.paged:
        dcfg = DistConfig(mesh=None)  # paged + seq-sharding: follow-up PR
    bundle = build_model(cfg, pol, dcfg, max_positions=args.capacity)
    params = bundle.init(jax.random.PRNGKey(args.seed))

    eng = Engine(bundle, n_slots=args.slots, capacity=args.capacity,
                 sampling=SamplingConfig(temperature=0.0))
    sched = ContinuousScheduler(eng, params, pad_prompt_to=args.prompt_len)
    toks = np.asarray(lm_tokens(args.seed, 0, args.n_requests, args.prompt_len, cfg.vocab))
    reqs = [Request(rid=i, tokens=toks[i, : args.prompt_len].tolist(),
                    max_new=args.max_new) for i in range(args.n_requests)]
    t0 = time.time()
    out = sched.run(reqs)
    wall = time.time() - t0
    total_tokens = sum(len(v) for v in out.values())
    report = {
        "arch": cfg.name, "policy": args.policy, "requests": len(reqs),
        "tokens": total_tokens, "wall_s": round(wall, 2),
        "tok_per_s": round(total_tokens / wall, 1),
        "decode_steps": sched.steps,
        "mean_occupancy": round(sched.mean_occupancy, 2),
    }
    if args.paged:
        report.update(sched.engine.pool_stats(), preemptions=sched.preemptions)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
