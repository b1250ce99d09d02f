"""Whether what the timed path served is correct.

Once the window has closed, a sample of the finished requests, drawn
from the seed and always holding the longest one, goes through the
configuration's plain reference: one causal pass over each prompt with
its served tokens.  For every served token the number read is how far
its logit lies below the reference's best logit at that position (0
when the served token is the reference's greedy choice).  The cell's
number is the widest such gap; it is compared with the limit the cell
file states.  Greedy tokens only: every request of every cell is
greedy.

With ``control`` set to a lower precision the same comparison reads
the control: the reference at that precision put in the program's
place, its token at each served position of the same sample being the
one it puts first there.
"""
from __future__ import annotations

import numpy as np


def sample(window, n: int, seed: int) -> list:
    """Up to ``n`` requests that finished with all their tokens, drawn
    from ``seed``; the longest (prompt plus output) is always in."""
    done = [r for r in window.served
            if r.finished == "length" and len(r.tokens) == r.spec.max_new]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.spec.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0xC4EC]))
    pick = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(ref, conf, weights, reqs, control=None) -> list:
    """Per request: the gap below the reference's best logit of each
    served token, or with ``control`` of the token the reference at
    that precision puts first at each served position."""
    out = []
    for r in reqs:
        p = len(r.spec.prompt)
        seq = np.concatenate([r.spec.prompt, np.asarray(r.tokens[:-1],
                                                        np.int32)])
        rows = np.arange(p - 1, p - 1 + len(r.tokens))
        lg = ref.logits_at(conf, weights, seq, rows, "highest")
        tok = (np.asarray(r.tokens) if control is None else
               ref.logits_at(conf, weights, seq, rows, control).argmax(-1))
        out.append(lg.max(-1) - lg[np.arange(len(tok)), tok])
    return out


def judge(ref, conf, weights, window, check: dict, seed: int,
          control=None) -> dict:
    """The comparison that decides ``correct``: the widest gap beside
    its limit, the sample's size, and the verdict."""
    reqs = sample(window, int(check["sample"]), seed)
    limit = float(check["max_logit_gap"])
    gaps = served_gaps(ref, conf, weights, reqs, control)
    widest = float(max(g.max() for g in gaps)) if gaps else None
    return {"correct": widest is not None and widest <= limit,
            "numbers": {"max_logit_gap": {"value": widest, "limit": limit},
                        "sample_requests": len(reqs),
                        "sample_tokens": int(sum(len(g) for g in gaps))}}
