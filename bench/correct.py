"""The comparison that decides `correct`.

Once the window has closed, a sample drawn from the seed of the requests
the server finished in it, always with the one that served the most
tokens, is run through the plain float32 reference: each prompt followed
by its served tokens, in one pass. At every served token the reference
gives the gap by which that token's logit lies below its best logit; the
widest gap over the sample is compared with the cell's limit
(`bench/limits/<cell>.json`). Every token is greedy, so a sound server
serves near-ties only.

The control puts the reference, computed with float8 matmul operands, in
the server's place: its best token at each of the same positions, scored
by the float32 reference the same way.
"""
from __future__ import annotations

import numpy as np


def finished(tracks: list, t_end: float) -> list:
    return [tr for tr in tracks
            if tr.reason in ("stop", "length") and tr.times
            and tr.times[-1] <= t_end and len(tr.tokens) > 0]


def sample(tracks: list, t_end: float, seed: int, min_tokens: int = 300,
           min_requests: int = 4, max_requests: int = 12) -> list:
    """The longest finished request, then others in seeded order, until
    both `min_tokens` served tokens and `min_requests` requests are in, or
    `max_requests` requests."""
    done = finished(tracks, t_end)
    if not done:
        return []
    done.sort(key=lambda tr: tr.rid)
    longest = max(done, key=lambda tr: (len(tr.tokens), -tr.rid))
    rest = [tr for tr in done if tr is not longest]
    order = np.random.default_rng(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 17]).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if (n >= min_tokens and len(out) >= min_requests) \
                or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def _sequence(tr):
    """(inputs, served): the prompt and all served tokens but the last are
    the inputs; served token j is predicted at input position
    len(prompt) - 1 + j."""
    prompt = list(tr.req.prompt)
    served = list(tr.tokens)
    return prompt + served[:-1], len(prompt) - 1, served


def gaps(ref, cfg: dict, w, reqs: list, control: bool = False) -> dict:
    """Widest reference logit gap over the served tokens of `reqs` (with
    `control`, over the float8 reference's own best tokens instead)."""
    worst, n = 0.0, 0
    per = []
    for tr in reqs:
        inputs, lo, served = _sequence(tr)
        targets = np.zeros(len(inputs), np.int32)
        targets[lo:lo + len(served)] = served
        if control:
            _, best8 = ref.score(cfg, w, inputs, targets, fp8=True)
            targets[lo:] = best8[lo:]
        gap, _ = ref.score(cfg, w, inputs, targets)
        g = float(np.max(gap[lo:lo + len(served)]))
        per.append([len(inputs) + 1, len(served), g])
        worst = max(worst, g)
        n += len(served)
    return {"max_logit_gap": worst, "tokens": n, "requests": len(reqs),
            "per_request": per}


def checks(limits: dict, reading: dict, failed: int) -> dict:
    """Each number compared, beside its limit. `correct` is all of them
    holding."""
    return {
        "max_logit_gap": {"value": reading["max_logit_gap"],
                          "limit": limits["max_logit_gap"], "holds": "<="},
        "requests_compared": {"value": reading["requests"], "limit": 1,
                              "holds": ">="},
        "failed_requests": {"value": failed, "limit": 0, "holds": "<="},
    }


def holds(c: dict) -> bool:
    v, lim = c["value"], c["limit"]
    return v <= lim if c["holds"] == "<=" else v >= lim
