"""The one traffic generator: reads a traffic mix (a JSON file of
parameters in ``bench/traffic/``) and a seed, and returns the requests.

The seed draws everything: the prefix choices, body and output lengths
and inter-arrival gaps, and the token ids.  Every ``block`` consecutive
requests hold, of each quantity, the values at the quantiles
``(i + 1/2) / block`` of its declared distribution, in an order drawn
from the seed; the gaps are scaled so that a block spans ``block /
rate`` seconds.  So every seed offers the same set of sizes in each
block, at the open loop's exact rate, and the schedule (which size
comes when, to which client) changes with the seed.  A window that
holds a few blocks then sees nearly the same work on every seed.

A mix that names ``sizes_seed`` draws its sizes, gaps and prefix
choices from that seed instead, so every run offers the one schedule
and the run's seed draws only the token ids.  Both committed mixes do:
on a TPU v5e, schedules drawn from the run's seed spread the runs of
one cell far more than repeats of one seed do (``doc_qa``'s
``output_tok_s`` 27.4 to 52.4 tokens/s over six seeds; ``chat_shared``'s
``ttft_p90_ms`` quartile spread 18-45%), beyond any bound a check can
hold a change to.

Mix keys:

* ``arrival``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop,
  exponential gaps), ``{"kind": "gamma", "rate_per_s": r, "cv": c}``
  (open loop, bursty), or ``{"kind": "closed", "clients": n,
  "stagger_s": s}`` (client ``i`` sends its first request at
  ``i * s`` and each next one when the previous finishes; request
  ``k`` of the sequence goes to client ``k % n``).
* ``prefixes``: optional ``{"count": k, "tokens": t, "zipf_s": s}``,
  shared system prompts; each request starts with one of them, chosen
  by Zipf(s) over their ranks.
* ``body``, ``output``: lengths as ``{"median": m, "sigma": s,
  "min": lo, "max": hi}``, lognormal and clipped to ``[lo, hi]``.
* ``block``: see above; a closed loop's block is one request for each
  client.
* ``sizes_seed``: optional, see above.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
from scipy.special import gammaincinv, ndtri


@dataclass
class Spec:
    """One request as offered: ``due`` is seconds after the traffic
    starts (open loop only; a closed-loop client sends when ready)."""
    prompt: np.ndarray
    max_new: int
    due: float = 0.0
    prefix: int = -1                   # shared prefix id, -1 = none


@dataclass
class Traffic:
    kind: str                          # 'open' | 'closed'
    open: list = field(default_factory=list)      # [Spec] by due time
    clients: list = field(default_factory=list)   # [[Spec]] per client
    starts: list = field(default_factory=list)    # first send per client


def _order(rng, n: int, block: int) -> np.ndarray:
    """``n`` quantiles: each run of ``block`` holds ``(i + 1/2) /
    block``, ``i < block``, in an order drawn from ``rng``."""
    k = -(-n // block)
    perm = np.argsort(rng.random((k, block)), axis=1)
    return ((perm + 0.5) / block).reshape(-1)[:n]


def _lognormal(rng, p: dict, n: int, block: int) -> np.ndarray:
    z = ndtri(_order(rng, n, block))
    x = np.exp(np.log(p["median"]) + p["sigma"] * z)
    return np.clip(np.rint(x), p["min"], p["max"]).astype(np.int64)


def _zipf(rng, count: int, s: float, n: int, block: int) -> np.ndarray:
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    return np.minimum(np.searchsorted(cdf, _order(rng, n, block)),
                      count - 1)


def _gaps(rng, arr: dict, n: int, block: int) -> np.ndarray:
    if arr["kind"] == "closed":
        return np.zeros(n)
    if arr["kind"] == "poisson":
        def ppf(u):
            return -np.log1p(-u)
    elif arr["kind"] == "gamma":
        def ppf(u):
            return gammaincinv(1.0 / arr["cv"] ** 2, u)
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    mean = ppf((np.arange(block) + 0.5) / block).mean()
    return ppf(_order(rng, n, block)) / (mean * arr["rate_per_s"])


def sizes(mix: dict, n: int, rng) -> dict:
    """The first ``n`` requests' sizes and the gaps before them."""
    b = int(mix["block"])
    pre = mix.get("prefixes")
    return {"gap": _gaps(rng, mix["arrival"], n, b),
            "prefix": (_zipf(rng, pre["count"], pre["zipf_s"], n, b)
                       if pre else np.full(n, -1)),
            "body": _lognormal(rng, mix["body"], n, b),
            "output": _lognormal(rng, mix["output"], n, b)}


def generate(mix: dict, seed: int, horizon_s: float, vocab: int) -> Traffic:
    """Requests for ``horizon_s`` seconds of traffic: the open loop's
    ``rate * horizon_s`` arrivals (those past ``horizon_s`` left out),
    or for a closed loop as many requests as its clients could send at
    one a second."""
    arr = mix["arrival"]
    closed = arr["kind"] == "closed"
    if closed:
        n = int(arr["clients"] * horizon_s) + 1
    else:
        n = int(np.ceil(arr["rate_per_s"] * horizon_s))
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0x7E57]))
    sz = sizes(mix, n, np.random.default_rng(int(mix["sizes_seed"]))
               if "sizes_seed" in mix else rng)
    pre = mix.get("prefixes")
    prefixes = ([rng.integers(1, vocab, size=pre["tokens"])
                 .astype(np.int32) for _ in range(pre["count"])]
                if pre else [])
    due = np.cumsum(sz["gap"])
    specs = []
    for i in range(n):
        if not closed and due[i] > horizon_s:
            break
        body = rng.integers(1, vocab, size=int(sz["body"][i])).astype(
            np.int32)
        k = int(sz["prefix"][i])
        prompt = np.concatenate([prefixes[k], body]) if k >= 0 else body
        specs.append(Spec(prompt=prompt, max_new=int(sz["output"][i]),
                          due=0.0 if closed else float(due[i]), prefix=k))
    if not closed:
        return Traffic(kind="open", open=specs)
    c = int(arr["clients"])
    return Traffic(kind="closed", clients=[specs[i::c] for i in range(c)],
                   starts=[i * float(arr["stagger_s"]) for i in range(c)])
