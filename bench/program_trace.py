"""Read the program's own names in a profiler trace (``.xplane.pb``).

The serving loop writes its phases as host spans ``serve.*``
(``repro.serving.spans``); each step program runs under its own name in
a device plane's ``XLA Modules`` line (``jit_packed_step(<id>)``,
``jit_decode_step(<id>)``, ...); and each device operation carries the
``jax.named_scope`` path it was traced under (``.../kv_gather/...``)
in a stat of its event metadata (``SCOPE_STAT``).  A trace of a program
without these names (one recorded before they existed) gives ``None``
for every reading here, never an error.

``jax.profiler.ProfileData`` leaves event metadata stats out, so
``read`` decodes the protobuf itself, keeping only what is read here.
Times are nanoseconds on the trace's one clock; the window ``(t0, t1)``
and the device's busy intervals come from ``trace_reduce.reduce_trace``,
so every reading here covers the window ``device_idle_pct`` covers.
"""
from __future__ import annotations

import bisect
import re
import struct
from dataclasses import dataclass, field

from .trace_reduce import _MOD, _self_times, op_name

#: The program's host spans, and among them the host phases: the loop's
#: own work, as against ``serve.wait`` (the host waits for the device),
#: ``serve.spill`` (a device-to-host gather) and ``serve.step`` (the
#: whole iteration).
PREFIX = "serve."
HOST_PHASES = ("serve.admit", "serve.plan", "serve.launch",
               "serve.reconcile", "serve.flush")
#: The step programs, by the name ``jax.jit`` gives them.
STEP_PROGRAMS = ("jit_packed_step", "jit_decode_step",
                 "jit_chunk_prefill_step", "jit_prefill_step")
#: The event-metadata stat that holds an operation's scope path in a TPU
#: trace: its HLO ``op_name`` as ``<path>:<type>``, the type mostly
#: empty (``jit(packed_step)/while/body/closed_call/kv_gather/jit(_take)/
#: gather:``).
SCOPE_STAT = "tf_op"


@dataclass
class Plane:
    name: str
    lines: dict = field(default_factory=dict)   # name -> [Event]


@dataclass
class Event:
    start_ns: float
    end_ns: float
    name: str
    stats: dict                        # metadata stats, then the event's


# -- protobuf wire format (XSpace, tsl/profiler/protobuf/xplane.proto) --

def _varint(buf, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint or a
    fixed64 field, a ``memoryview`` for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif kind == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif kind == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {kind} in an XSpace")
        yield key >> 3, v


def _i64(v: int) -> int:
    """An ``int64`` field's varint, two's complement."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names: dict):
    """XStat -> (name, value); a ``ref_value`` is the name of the stat
    metadata it refers to, as the profiler interns strings."""
    name, val = None, None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, v)
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            val = _i64(v) if f == 4 else v
        elif f == 5:
            val = _str(v)
        elif f == 7:
            val = stat_names.get(v, v)
    return name, val


def _plane(buf) -> Plane:
    name, lines, ev_meta, st_meta = "", [], [], {}
    for f, v in _fields(buf):
        if f == 2:
            name = _str(v)
        elif f == 3:
            lines.append(v)
        elif f in (4, 5):
            entry = dict(_fields(v))
            if f == 4:
                ev_meta.append(entry.get(2, b""))
            else:
                meta = dict(_fields(entry.get(2, b"")))
                st_meta[entry.get(1, 0)] = _str(meta.get(2, b""))
    metas = {}
    for m in ev_meta:
        mid, mname, stats = 0, "", {}
        for f, v in _fields(m):
            if f == 1:
                mid = v
            elif f == 2:
                mname = _str(v)
            elif f == 5:
                k, val = _stat(v, st_meta)
                stats[k] = val
        metas[mid] = (mname, stats)
    pl = Plane(name=name)
    for ln in lines:
        lname, ts, evs = "", 0, []
        for f, v in _fields(ln):
            if f == 2:
                lname = _str(v)
            elif f == 3:
                ts = _i64(v)
            elif f == 4:
                evs.append(v)
        out = []
        for ev in evs:
            mid = off = dur = 0
            own = None
            for f, v in _fields(ev):
                if f == 1:
                    mid = v
                elif f == 2:
                    off = _i64(v)
                elif f == 3:
                    dur = _i64(v)
                elif f == 4:
                    own = own or {}
                    k, val = _stat(v, st_meta)
                    own[k] = val
            mname, stats = metas.get(mid, ("", {}))
            if own:
                stats = {**stats, **own}
            a = ts + off / 1e3
            out.append(Event(a, a + dur / 1e3, mname, stats))
        pl.lines[lname] = out
    return pl


def read(data) -> list:
    """The planes of a serialized XSpace (bytes or a path)."""
    if not isinstance(data, (bytes, bytearray, memoryview)):
        with open(data, "rb") as f:
            data = f.read()
    return [_plane(v) for f, v in _fields(memoryview(data)) if f == 1]


# -- readings -------------------------------------------------------------

def device_planes(planes) -> list:
    return [p for p in planes if re.match(r"^/device:[A-Z]+:\d+$", p.name)
            and not p.name.startswith("/device:CUSTOM")]


def program_spans(planes) -> list:
    """(start, end, name) of the program's host spans."""
    return sorted((e.start_ns, e.end_ns, e.name.split("#")[0])
                  for p in planes if p.name.startswith("/host:")
                  for evs in p.lines.values() for e in evs
                  if e.name.startswith(PREFIX))


def innermost(spans) -> list:
    """The timeline under ``spans`` cut into [start, end, name] pieces,
    each named by the innermost span covering it: the latest started
    (the shortest, among spans that start together)."""
    spans = sorted(spans)
    bounds = sorted({t for s in spans for t in s[:2]})
    out, active, j = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while j < len(spans) and spans[j][0] <= a:
            active.append(spans[j])
            j += 1
        active = [s for s in active if s[1] > a]
        if not active:
            continue
        name = max(active, key=lambda s: s[0])[2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1][1] = b
        else:
            out.append([a, b, name])
    return out


def idle(busy: list, t0: float, t1: float) -> list:
    """The stretches of ``[t0, t1]`` outside the merged ``busy``
    intervals."""
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if cur < t1:
        gaps.append((cur, t1))
    return gaps


def _cover(pieces: list, starts: list, a: float, b: float) -> dict:
    """Time of ``[a, b]`` under each name of the sorted, disjoint
    ``pieces``, names in the order they first appear."""
    out = {}
    k = max(bisect.bisect_right(starts, a) - 1, 0)
    while k < len(pieces) and pieces[k][0] < b:
        s0, s1, nm = pieces[k]
        ov = min(b, s1) - max(a, s0)
        if ov > 0:
            out[nm] = out.get(nm, 0.0) + ov
        k += 1
    return out


def labelled_gaps(busy: list, t0: float, t1: float, spans: list) -> list:
    """(start, end, label) of each of the device's idle stretches in
    ``[t0, t1]``, labelled by the innermost host span covering most of
    it ('no host span' otherwise)."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    out = []
    for a, b in idle(busy, t0, t1):
        cover = _cover(pieces, starts, a, b)
        out.append((a, b, max(cover, key=cover.get) if cover
                    else "no host span"))
    return out


def label_gaps(busy: list, t0: float, t1: float, spans: list,
               n: int = 10) -> list:
    """The ``n`` longest idle stretches as (label, seconds).  Over spans
    that do not nest (the benchmark's own) these are the labels
    ``trace_reduce`` gives."""
    gaps = [(nm, (b - a) / 1e9)
            for a, b, nm in labelled_gaps(busy, t0, t1, spans)]
    gaps.sort(key=lambda g: -g[1])
    return gaps[:n]


def idle_by_span(busy: list, t0: float, t1: float, spans: list) -> dict:
    """Idle seconds of ``[t0, t1]`` by the innermost span over them."""
    pieces = innermost(spans)
    starts = [p[0] for p in pieces]
    out: dict = {}
    for a, b in idle(busy, t0, t1):
        for nm, s in _cover(pieces, starts, a, b).items():
            out[nm] = out.get(nm, 0.0) + s / 1e9
    return out


def host_stall_pct(planes, busy: list, t0: float, t1: float):
    """Share of the window in which no device operation runs and the
    innermost program span is a host phase, in percent; None without
    program spans."""
    spans = program_spans(planes)
    if not spans:
        return None
    by = idle_by_span(busy, t0, t1, spans)
    return 100.0 * sum(by.get(p, 0.0) for p in HOST_PHASES) / (
        (t1 - t0) / 1e9)


def _modules(plane) -> list:
    """(start, end, program name) of a device plane's executions."""
    out = []
    for e in plane.lines.get("XLA Modules", []):
        m = _MOD.match(e.name)
        out.append((e.start_ns, e.end_ns, m.group(1) if m else e.name))
    return out


def program_ms(planes, name: str, t0: float, t1: float):
    """Mean device milliseconds of the executions of program ``name``
    that lie inside the window, over every chip's; None without one."""
    ds = [(b - a) / 1e6 for p in device_planes(planes)
          for a, b, nm in _modules(p) if nm == name and a >= t0 and b <= t1]
    return sum(ds) / len(ds) if ds else None


def in_scope(stats: dict, scope: str) -> bool:
    """Whether an operation's scope path holds the scope ``scope``."""
    path = stats.get(SCOPE_STAT)
    return isinstance(path, str) and scope in path.split(":")[0].split("/")


def scope_pct(planes, scope: str, t0: float, t1: float):
    """Self time of the operations under ``scope`` over the device time
    of the step programs, both clipped to the window and summed over
    the chips, in percent; None when no step program ran there."""
    steps = ops = 0.0
    for p in device_planes(planes):
        for a, b, nm in _modules(p):
            if nm in STEP_PROGRAMS and min(b, t1) > max(a, t0):
                steps += min(b, t1) - max(a, t0)
        evs = [(max(e.start_ns, t0), min(e.end_ns, t1),
                in_scope(e.stats, scope))
               for e in p.lines.get("XLA Ops", [])
               if min(e.end_ns, t1) > max(e.start_ns, t0)]
        ops += _self_times(evs)[0].get(True, 0.0)
    return 100.0 * ops / steps if steps else None


def scope_ops(planes, scope: str, t0: float, t1: float) -> dict:
    """Self seconds of each operation name, and of it under ``scope``:
    {op: (total, in scope)}, per chip (mean)."""
    devs = device_planes(planes)
    out: dict = {}
    for p in devs:
        evs = [(max(e.start_ns, t0), min(e.end_ns, t1),
                (op_name(e.name), in_scope(e.stats, scope)))
               for e in p.lines.get("XLA Ops", [])
               if min(e.end_ns, t1) > max(e.start_ns, t0)]
        for (nm, hit), ns in _self_times(evs)[0].items():
            tot, inn = out.get(nm, (0.0, 0.0))
            out[nm] = (tot + ns / 1e9 / len(devs),
                       inn + (ns / 1e9 / len(devs) if hit else 0.0))
    return out


def reduce_program(planes, reduced) -> dict:
    """Every reading of this module over ``reduced``'s window (a
    ``trace_reduce.Reduced`` of the same trace)."""
    t0, t1 = reduced.t0, reduced.t1
    busy = reduced.chips[0].intervals
    return {
        "packed_ms": program_ms(planes, "jit_packed_step", t0, t1),
        "decode_ms": program_ms(planes, "jit_decode_step", t0, t1),
        "kv_gather_pct": scope_pct(planes, "kv_gather", t0, t1),
        "host_stall_pct": host_stall_pct(planes, busy, t0, t1),
    }
