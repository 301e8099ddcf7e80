"""The comparison that decides ``correct``.

After the window, a sample of the sessions the window served, drawn from
the seed and always holding the longest, is run through the plain
reference that the configuration names (``harness.Block``) once over
each prompt with the tokens the program served.  At every served position the reference's logit of the served token is read
against the reference's best logit there; the widest such gap over the
sample is the number held to the cell's limit (greedy decoding serves the
reference's argmax up to rounding, so a sound run reads near 0).

The control reads the same gap for the token the float8 reference puts
first at each position.
"""
from __future__ import annotations

import numpy as np


def sample(sessions: list[dict], n: int, seed: int) -> list[dict]:
    """``n`` sessions: the longest, then others drawn from ``seed``."""
    order = sorted(range(len(sessions)),
                   key=lambda i: -(len(sessions[i]["prompt"]) + len(sessions[i]["out"])))
    rest = list(np.random.default_rng(seed).permutation(order[1:]))
    return [sessions[i] for i in [order[0]] + rest[: max(0, n - 1)]]


def gaps(block, config: dict, dep: dict, params, sessions: list[dict], *,
         lowp: bool = False) -> list[np.ndarray]:
    """Per session, the gap at each served position: the reference's best
    logit less its logit of the served token (``lowp``: of the token the
    float8 control puts first).  ``block``: the configuration's
    ``harness.Block``, whose architecture module hands the program's
    weights to its reference."""
    ref_logits = block.reference.logits
    head = block.arch.head_view(params, config["vocab_size"])
    layer_w = lambda l: block.arch.layer_view(params, l)
    out = []
    for s in sessions:
        prompt, served = np.asarray(s["prompt"]), np.asarray(s["out"])
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        ref = ref_logits(config, dep, layer_w, head, toks, len(prompt) - 1)
        if lowp:
            ctrl = ref_logits(config, dep, layer_w, head, toks, len(prompt) - 1,
                              lowp=True)
            served = ctrl.argmax(-1)
        out.append(ref.max(-1) - ref[np.arange(len(served)), served])
    return out
