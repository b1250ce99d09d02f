"""The traffic generator: one seed, one set of requests; every seed the
same set of sizes in each block, in its own order; the distributions
the mix declares."""
import numpy as np
import pytest

from bench import generator as G
from bench import spec

CHAT = {"arrival": {"kind": "poisson", "rate_per_s": 2.0},
        "prefixes": {"count": 8, "tokens": 128, "zipf_s": 1.1},
        "body": {"median": 96, "sigma": 0.8, "min": 16, "max": 512},
        "output": {"median": 64, "sigma": 0.6, "min": 8, "max": 256},
        "block": 16}
DOC = {"arrival": {"kind": "closed", "clients": 8, "stagger_s": 6.0},
       "body": {"median": 2048, "sigma": 0.4, "min": 1024, "max": 3584},
       "output": {"median": 160, "sigma": 0.5, "min": 64, "max": 480},
       "block": 8}


def _key(t):
    specs = t.open or [s for c in t.clients for s in c]
    return [(s.due, s.max_new, s.prefix, s.prompt.tobytes()) for s in specs]


@pytest.mark.parametrize("mix", [CHAT, DOC], ids=["open", "closed"])
def test_same_seed_same_requests(mix):
    big = 2**31 + 12345
    assert _key(G.generate(mix, big, 60, 50257)) == \
        _key(G.generate(mix, big, 60, 50257))


@pytest.mark.parametrize("mix", [CHAT, DOC], ids=["open", "closed"])
def test_seeds_share_sizes_not_tokens(mix):
    b = mix["block"]
    a = G.sizes(mix, 10 * b, np.random.default_rng(1))
    c = G.sizes(mix, 10 * b, np.random.default_rng(2))
    for k in a:
        for i in range(0, 10 * b, b):              # each block, one set
            assert np.array_equal(np.sort(a[k][i:i + b]),
                                  np.sort(c[k][i:i + b]))
            assert np.array_equal(np.sort(a[k][i:i + b]),
                                  np.sort(a[k][:b]))
    assert not np.array_equal(a["body"], c["body"])
    ka = _key(G.generate(mix, 1, 60, 50257))
    kb = _key(G.generate(mix, 2, 60, 50257))
    assert [k[:3] for k in ka] != [k[:3] for k in kb]
    assert [k[3] for k in ka] != [k[3] for k in kb]


def test_sizes_seed_fixes_the_schedule():
    mix = dict(DOC, sizes_seed=7)
    ka = _key(G.generate(mix, 1, 60, 64000))
    kb = _key(G.generate(mix, 2**31 + 3, 60, 64000))
    assert [k[:3] for k in ka] == [k[:3] for k in kb]
    assert [len(k[3]) for k in ka] == [len(k[3]) for k in kb]
    assert [k[3] for k in ka] != [k[3] for k in kb]


def test_open_loop_offers_its_rate():
    """Each block of 16 arrivals spans 16 / rate seconds exactly."""
    for seed in (1, 2, 2**31 + 7):
        t = G.generate(CHAT, seed, 120, 50257)
        due = np.array([s.due for s in t.open])
        assert 239 <= len(due) <= 240                 # 2 / s for 120 s
        assert due[15::16] == pytest.approx(8.0 * np.arange(1, 16)[
            :len(due[15::16])])


def test_open_loop_distributions():
    t = G.generate(CHAT, 5, 2000, 50257)
    gaps = np.diff([0.0] + [s.due for s in t.open])
    assert abs(gaps.mean() - 0.5) < 0.05          # Poisson at 2 / s
    body = np.array([len(s.prompt) - 128 for s in t.open])
    out = np.array([s.max_new for s in t.open])
    assert body.min() >= 16 and body.max() <= 512
    assert abs(np.median(body) - 96) < 10
    assert out.min() >= 8 and out.max() <= 256
    assert abs(np.median(out) - 64) < 7
    counts = np.bincount([s.prefix for s in t.open], minlength=8)
    # Zipf: each block of 16 holds the ranks at its 16 quantiles
    assert (counts[:5] >= counts[1:6]).all()
    assert (counts % (len(t.open) // 16) == 0).all()
    assert counts[0] > 0.25 * len(t.open)
    # every request starts with its shared prefix
    heads = {}
    for s in t.open:
        heads.setdefault(s.prefix, s.prompt[:128].tobytes())
        assert s.prompt[:128].tobytes() == heads[s.prefix]
    assert all(s.prompt.min() >= 1 and s.prompt.max() < 50257
               for s in t.open)


def test_closed_loop_clients():
    t = G.generate(DOC, 9, 100, 64000)
    assert t.kind == "closed" and len(t.clients) == 8
    assert t.starts == [6.0 * i for i in range(8)]
    body = np.array([len(s.prompt) for c in t.clients for s in c])
    assert body.min() >= 1024 and body.max() <= 3584
    assert abs(np.median(body) - 2048) < 150


def test_gamma_arrivals_are_bursty():
    mix = dict(CHAT, arrival={"kind": "gamma", "rate_per_s": 2.0, "cv": 3})
    mix["block"] = 256
    gaps = np.diff([s.due for s in G.generate(mix, 1, 4000, 100).open])
    assert abs(gaps.mean() - 0.5) < 0.1
    assert gaps.std() / gaps.mean() > 2.0


@pytest.mark.parametrize("name", ["chat_shared", "doc_qa"])
def test_committed_mixes_generate(name):
    mix = spec.traffic(name)
    t = G.generate(mix, 3, 30, 1000)
    assert t.open or any(t.clients)
