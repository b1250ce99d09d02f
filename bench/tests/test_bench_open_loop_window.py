"""Why ``output_tok_s`` is not reported in the open-loop chat cell.

``chat_shared`` fixes its schedule (``sizes_seed``), and the cell runs
below the knee, so the engine keeps up: the tokens it delivers in the
window are the tokens offered, shifted by how late it delivers them.
Tokens per second there reads the engine's lag, not its capacity, and
the cell is judged on its tails instead."""
from types import SimpleNamespace as NS

import pytest

from bench import generator as G
from bench import spec
from bench.view import View

CHAT = "gpt2s-chat-1c"
#: Tokens the requests due in the chat window ask for: 64.8 a second
#: over a 50 s window.
OFFERED = 3240


def _window():
    """The chat cell's window on the traffic's clock, and its traffic."""
    bm, cell = spec.benchmark(), spec.cell(CHAT)
    t0 = float(cell["preroll_s"])
    t1 = t0 + bm["run_seconds"]
    mix = spec.traffic(cell["traffic"])
    vocab = spec.config(cell["config"])["vocab_size"]
    return t0, t1, lambda seed: G.generate(mix, seed, t1, vocab).open


def _schedule(specs):
    return [(s.due, s.max_new, s.prefix, len(s.prompt)) for s in specs]


@pytest.mark.parametrize("seed", [1, 12345, 3000000901, 2**31 + 12345])
def test_chat_schedule_is_the_same_for_every_seed(seed):
    t0, t1, requests = _window()
    specs = requests(seed)
    assert _schedule(specs) == _schedule(requests(0))
    assert sum(s.max_new for s in specs if t0 <= s.due < t1) == OFFERED


def _tok_s(specs, t0, t1, delay):
    """``output_tok_s`` of a window in which every token of a request
    arrives ``delay`` seconds after the request was due."""
    served = [NS(due=s.due, times=[s.due + delay] * s.max_new)
              for s in specs]
    v = View(window=NS(t0=t0, t1=t1, served=served), setup_s=0.0,
             shape=None, peaks={}, chips=1)
    return spec.reader("output_tok_s")(v)


@pytest.mark.parametrize("delay", [6.0, 7.0, 8.0])
def test_chat_tok_s_moves_with_the_lag_beyond_its_bound(delay):
    t0, t1, requests = _window()
    specs = requests(3000013103)
    on_time = _tok_s(specs, t0, t1, 0.0)
    assert on_time == pytest.approx(OFFERED / (t1 - t0))
    bound = next(m["bound"] for m in spec.benchmark()["end_to_end"]
                 if m["name"] == "output_tok_s")
    assert abs(_tok_s(specs, t0, t1, delay) / on_time - 1) > bound


@pytest.mark.parametrize("cell, names", [
    (CHAT, ["ttft_p50_ms", "ttft_p90_ms", "itl_p95_ms", "setup_s"]),
    ("yi6b-doc-1c", ["output_tok_s", "itl_p95_ms", "setup_s"]),
])
def test_end_to_end_metrics_of_each_cell(cell, names):
    bm = spec.benchmark()
    assert [m["name"] for m in spec.metrics_for(bm, cell, False)] == names
    # every per-layer metric of the cell moves a metric the cell reports
    for m in spec.metrics_for(bm, cell, True):
        assert m["moves"] in names, m["name"]
