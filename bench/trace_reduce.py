"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

Device planes are ``/device:<KIND>:<n>``.  Their ``XLA Ops`` line
holds every operation, nested (a ``while`` holds its body), each named
``%<op>.<k> = <shape> ...``; ``XLA Modules`` holds each program
execution, named ``<program>(<id>)``.  The host plane ``/host:CPU``
holds the benchmark's own ``TraceAnnotation`` spans.  Times are
nanoseconds on one clock.

* busy: the union of the device's operation intervals inside the
  traced window;
* ops: each operation's self time (its length less that of the
  operations nested in it), summed by its name without the ``.<k>``;
* idle gaps: the stretches of the window with no operation running,
  each labelled by the host span that covers most of it.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

_OP = re.compile(r"^%?([^\s=]+?)(\.\d+)?\s*=")
_MOD = re.compile(r"^(.*)\((\d+)\)$")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def op_name(event_name: str) -> str:
    """``%flash_decode_stats.8 = (f32[...]) ...`` -> ``flash_decode_stats``."""
    m = _OP.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0].lstrip("%")


@dataclass
class Chip:
    busy_ns: float = 0.0
    intervals: list = field(default_factory=list)    # merged busy
    op_self_ns: dict = field(default_factory=lambda: defaultdict(float))
    op_calls: dict = field(default_factory=lambda: defaultdict(int))
    collective_exposed_ns: float = 0.0


@dataclass
class Reduced:
    t0: float                          # window start, ns
    t1: float                          # window end, ns
    chips: list                        # [Chip] per device plane
    gaps: list                         # [(label, seconds)] longest first
    host_t0: float = 0.0               # first host span's start, ns

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @property
    def busy_s(self) -> float:
        return sum(c.busy_ns for c in self.chips) / len(self.chips) / 1e9

    def op_seconds(self, name: str) -> float:
        """Self seconds of operation ``name`` per chip (mean)."""
        return (sum(c.op_self_ns.get(name, 0.0) for c in self.chips)
                / len(self.chips) / 1e9)

    def op_calls(self, name: str) -> float:
        return sum(c.op_calls.get(name, 0) for c in self.chips) / len(
            self.chips)

    def top_ops(self, n: int = 10) -> list:
        tot = defaultdict(float)
        for c in self.chips:
            for k, v in c.op_self_ns.items():
                tot[k] += v / len(self.chips) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def _merge(iv: list) -> list:
    iv.sort()
    out = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _self_times(events: list) -> dict:
    """Self time per op name over nested (start, end, name) events."""
    out = defaultdict(float)
    calls = defaultdict(int)
    stack: list = []                   # [end, name, child_ns]
    for a, b, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            end, nm, child, start = stack.pop()
            out[nm] += (end - start) - child
            if stack:
                stack[-1][2] += end - start
        stack.append([b, name, 0.0, a])
        calls[name] += 1
    while stack:
        end, nm, child, start = stack.pop()
        out[nm] += (end - start) - child
        if stack:
            stack[-1][2] += end - start
    return out, calls


def _clip(events, t0, t1):
    for e in events:
        a, b = max(e.start_ns, t0), min(e.end_ns, t1)
        if b > a:
            yield a, b, e


def host_spans(pd, names) -> list:
    """(start, end, name) of host events whose name is in ``names``."""
    out = []
    for pl in pd.planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name in names:
                    out.append((e.start_ns, e.end_ns, e.name))
    return out


def _device_planes(pd) -> list:
    return [pl for pl in pd.planes
            if re.match(r"^/device:[A-Z]+:\d+$", pl.name)
            and not pl.name.startswith("/device:CUSTOM")]


def reduce_trace(pd, labels=("engine.step", "bench.submit",
                             "bench.deliver", "bench.idle"),
                 window=None, lead_s: float = 0.0) -> Reduced:
    """``pd``: a ``jax.profiler.ProfileData``.  The window runs from
    ``lead_s`` after the first of the host ``labels`` spans to the end
    of the last, unless given as ``(t0, t1)`` ns.  The lead-in is
    profiled and not counted: the device side of a trace starts
    recording some time after the host side (up to 230 ms on a v5e),
    so what is idle inside the window is idle."""
    spans = host_spans(pd, set(labels))
    devs = []                          # XLA Ops events per device
    for pl in _device_planes(pd):
        lines = {ln.name: list(ln.events) for ln in pl.lines}
        if lines.get("XLA Ops"):
            devs.append(lines["XLA Ops"])
    if not devs:
        raise ValueError("the trace holds no device operations")
    if window is None:
        if not spans:
            raise ValueError("the trace holds none of the host spans "
                             f"{labels}")
        window = (min(s[0] for s in spans) + lead_s * 1e9,
                  max(s[1] for s in spans))
    t0, t1 = window
    chips = []
    for op_events in devs:
        chip = Chip()
        ops, coll, comp = [], [], []
        for a, b, e in _clip(op_events, t0, t1):
            nm = op_name(e.name)
            ops.append((a, b, nm))
            (coll if nm.startswith(COLLECTIVES) else comp).append([a, b])
        chip.intervals = _merge([[a, b] for a, b, _ in ops])
        chip.busy_ns = sum(b - a for a, b in chip.intervals)
        chip.op_self_ns, chip.op_calls = _self_times(ops)
        chip.collective_exposed_ns = _exposed(_merge(coll), _merge(comp))
        chips.append(chip)
    return Reduced(t0=t0, t1=t1, chips=chips,
                   gaps=_label_gaps(chips[0].intervals, t0, t1, spans),
                   host_t0=min((s[0] for s in spans), default=t0))


def _exposed(coll: list, comp: list) -> float:
    """Length of ``coll`` intervals not covered by ``comp`` ones (both
    merged and sorted)."""
    tot, j = 0.0, 0
    for a, b in coll:
        cur = a
        while j < len(comp) and comp[j][1] <= cur:
            j += 1
        k = j
        while k < len(comp) and comp[k][0] < b:
            if comp[k][0] > cur:
                tot += comp[k][0] - cur
            cur = max(cur, comp[k][1])
            k += 1
        if cur < b:
            tot += b - cur
    return tot


def _label_gaps(busy: list, t0: float, t1: float, spans: list,
                n: int = 10) -> list:
    """The device's idle stretches in ``[t0, t1]``, each labelled by
    the host span covering most of it ('no host span' otherwise);
    returns the ``n`` longest as (label, seconds), and their total by
    label under the key ``'total:<label>'`` is left to the caller."""
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    spans = sorted(spans)
    out = []
    for a, b in gaps:
        cover = defaultdict(float)
        for s0, s1, nm in spans:
            if s1 <= a:
                continue
            if s0 >= b:
                break
            cover[nm] += min(b, s1) - max(a, s0)
        label = max(cover, key=cover.get) if cover else "no host span"
        out.append((label, (b - a) / 1e9))
    out.sort(key=lambda g: -g[1])
    return out[:n]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))
