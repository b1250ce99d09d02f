"""The metric readers' shared arithmetic: percentiles, the window's
tokens and first tokens, and the shares of peaks and rooflines."""
import math
from types import SimpleNamespace as NS

import numpy as np
import pytest

from bench import flops as F
from bench import spec
from bench.view import View, percentile


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50
    assert percentile(xs, 90) == 90
    assert percentile(xs, 95) == 95
    assert percentile([3.0], 95) == 3.0
    assert percentile([1, 2, math.inf], 50) == 2
    assert math.isnan(percentile([], 50))


def _req(due, times, prompt=10, max_new=None):
    return NS(due=due, times=list(times),
              spec=NS(prompt=np.zeros(prompt, np.int32),
                      max_new=max_new or len(times)))


def _view(served, steps=(), kernel_s=0.0, span=(0.0, 10.0)):
    w = NS(t0=1.0, t1=3.0, served=served, steps=list(steps),
           trace_span=span, occupancy=[0.5, 1.0],
           stats0={"host_busy_s": 1.0, "prefix_tokens_saved": 0,
                   "packed_prefill_tokens": 0},
           stats1={"host_busy_s": 1.5, "prefix_tokens_saved": 30,
                   "packed_prefill_tokens": 90})
    trace = NS(op_seconds=lambda name: kernel_s, t0=5.0,
               t1=5.0 + 1e9 * (span[1] - span[0]), host_t0=5.0)
    return View(window=w, setup_s=7.0, shape=F.Shape(2, 8, 2, 2, 4, 16, 32,
                                                     False),
                peaks=spec.read_json(spec.BENCH / "peaks.json")[
                    "TPU v5 lite"], chips=1, trace=trace)


def test_window_readers():
    served = [_req(0.5, [1.2, 1.4]),          # due before the window
              _req(1.5, [2.0, 2.5, 3.5]),     # one token after it
              _req(2.0, []),                  # never answered
              _req(3.5, [3.6])]               # due after it
    v = _view(served)
    assert v.seconds == 2.0
    assert len(v.tokens_between(1.0, 3.0)) == 4
    assert spec.reader("output_tok_s")(v) == 2.0
    # due in the window: 0.5 s, and the unanswered one counts to the
    # last token seen (3.6 s)
    assert sorted(v.ttfts()) == pytest.approx([0.5, 1.6])
    assert spec.reader("ttft_p50_ms")(v) == pytest.approx(500.0)
    assert spec.reader("ttft_p90_ms")(v) == pytest.approx(1600.0)
    # gaps with both tokens in the window: 0.2 and 0.5
    assert spec.reader("itl_p95_ms")(v) == pytest.approx(500.0)
    assert spec.reader("host_busy_pct")(v) == pytest.approx(25.0)
    assert spec.reader("occupancy_pct")(v) == pytest.approx(75.0)
    assert spec.reader("prefix_hit_pct")(v) == pytest.approx(25.0)
    assert spec.reader("setup_s")(v) == 7.0




def test_step_mfu_counts_the_window_tokens():
    """A 10-token prompt's forward pass and one decode position, each
    with its output head: (24000 + 512) + (2752 + 512) FLOPs by hand
    for the shape of ``_view``, over 2 s of a 197 TFLOP/s chip."""
    v = _view([_req(1.5, [2.0, 2.5], prompt=10)])
    v.window.stats1 = dict(v.window.stats0)          # no prefix hits
    assert spec.reader("step_mfu_pct")(v) == pytest.approx(
        100.0 * 27776 / (197e12 * 2.0))


@pytest.mark.parametrize("kernel_s,want", [(1e-8, 100.0 * 2560 / 819e9
                                            / 1e-8), (0.0, None)],
                         ids=["kernel_ran", "kernel_absent"])
def test_flash_decode_roofline_is_memory_bound(kernel_s, want):
    """The same two tokens read 10 positions' keys and values twice
    (1280 bytes each, 2 layers); the bytes bound the time.  With no
    kernel time in the trace the reader returns nothing."""
    v = _view([_req(1.5, [2.0, 2.5], prompt=10)], kernel_s=kernel_s)
    got = spec.reader("flash_decode_roofline_pct")(v)
    assert got == (None if want is None else pytest.approx(want))
