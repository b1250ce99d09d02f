"""What one run measured, as the metric readers see it.

A reader in ``bench/metrics/<name>.py`` defines ``read(view)`` and
returns a number, or ``None`` when it finds nothing to read.  Times on
the engine clock are seconds; the traced window comes from the
profiler when the run was traced.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

from . import flops as F


@dataclass
class View:
    window: object                     # drive.Window
    setup_s: float
    shape: F.Shape                     # the model's sizes
    peaks: dict                        # bench/peaks.json entry
    chips: int
    trace: object = None               # trace_reduce.Reduced or None

    # -- the measured window ------------------------------------------
    @property
    def seconds(self) -> float:
        return self.window.t1 - self.window.t0

    def due_in_window(self) -> list:
        w = self.window
        return [r for r in w.served if w.t0 <= r.due < w.t1]

    def tokens_between(self, a: float, b: float) -> list:
        """(request, token index) of every token delivered in [a, b)."""
        out = []
        for r in self.window.served:
            for j, t in enumerate(r.times):
                if a <= t < b:
                    out.append((r, j))
        return out

    def ttfts(self) -> list:
        """Time to first token of each request due in the window; one
        that never got one counts from its due time to the end of the
        run, as a bound it exceeded."""
        end = max((t for r in self.window.served for t in r.times),
                  default=self.window.t1)
        return [(r.times[0] if r.times else max(end, r.due)) - r.due
                for r in self.due_in_window()]

    def stat(self, name: str) -> float:
        return self.window.stats1[name] - self.window.stats0[name]

    # -- the traced window --------------------------------------------
    def trace_span(self) -> tuple:
        """The reduced trace's window on the engine clock: the profile
        started at ``window.trace_span[0]``, where its first host span
        starts."""
        a = self.window.trace_span[0]
        t = self.trace
        return (a + (t.t0 - t.host_t0) / 1e9, a + (t.t1 - t.host_t0) / 1e9)


def percentile(xs: list, q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least
    ``q`` percent of the sample at or below it."""
    s = sorted(xs)
    if not s:
        return math.nan
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]
