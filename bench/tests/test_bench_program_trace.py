"""bench/program_trace.py on hand-built traces whose answers are known,
on the recorded trace of a program without spans or names
(``engine_v5e.xplane.pb``), and on a slice of a trace recorded on a TPU
v5e with them (``engine_v5e_spans.xplane.pb``: gpt2-small served by the
streaming engine, one packed-step execution and 15 ms on either side,
with the loop's ``serve.*`` spans and the benchmark's host spans, each
operation keeping only its scope stat)."""
from pathlib import Path
from types import SimpleNamespace as NS

import pytest
from jax.profiler import ProfileData

from bench import program_trace as PT
from bench import spec
from bench import trace_reduce as TR

FIX = Path(__file__).parent / "fixtures"
OLD = FIX / "engine_v5e.xplane.pb"
NEW = FIX / "engine_v5e_spans.xplane.pb"
KV = "jit(packed_step)/while/body/closed_call/kv_gather/gather"
ATTN = "jit(packed_step)/while/body/closed_call/attn_kernel/pallas_call"


def _serialized(planes: list) -> bytes:
    """planes: [(plane, {line: [(name, start_ns, dur_ns[, scope])]})];
    a scope rides as the operation's event-metadata stat."""
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        metas = {}
        body = [f'id: {pid} name: "{pname}"']
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = []
            for e in evs:
                key = (e[0], e[3] if len(e) > 3 else None)
                mid = metas.setdefault(key, len(metas) + 1)
                ev.append(f"events {{ metadata_id: {mid} offset_ps: "
                          f"{int(e[1] * 1000)} duration_ps: "
                          f"{int(e[2] * 1000)} }}")
            body.append(f'lines {{ id: {lid} name: "{lname}" '
                        f"timestamp_ns: 0 {' '.join(ev)} }}")
        for (n, scope), i in metas.items():
            st = (f'stats {{ metadata_id: 1 str_value: "{scope}" }}'
                  if scope else "")
            body.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" {st} }} }}')
        body.append(f'stat_metadata {{ key: 1 value {{ id: 1 name: '
                    f'"{PT.SCOPE_STAT}" }} }}')
        out.append("planes { " + " ".join(body) + " }")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def _trace(program_spans=True, named=True):
    """One chip.  Device: a packed step 100..500 (a while holding a
    kv_gather op and the kernel), a decode step 600..700 and a pack
    720..730.  Host: the benchmark's engine.step and bench.deliver
    spans; inside engine.step the loop's spans."""
    mod = ("jit_packed_step", "jit_decode_step") if named else (
        "jit_body_core", "jit_body_core")
    kv, attn = (KV, ATTN) if named else (None, None)
    dev = {"XLA Modules": [(f"{mod[0]}(1)", 100, 400),
                           (f"{mod[1]}(2)", 600, 100),
                           ("jit_pack(3)", 720, 10)],
           "XLA Ops": [("%while.3 = (f32[2]) while()", 100, 400),
                       ("%copy.1 = f32[1] copy()", 150, 100, kv),
                       ("%flash_decode_stats.8 = f32[1] custom-call()",
                        300, 100, attn),
                       ("%fusion.2 = f32[1] fusion()", 600, 100, kv),
                       ("%fusion.3 = f32[1] fusion()", 720, 10)]}
    host = [("engine.step", 0, 800), ("bench.deliver", 800, 200)]
    if program_spans:
        host += [("serve.step", 10, 780), ("serve.admit", 20, 40),
                 ("serve.plan", 60, 20), ("serve.launch", 80, 40),
                 ("serve.wait", 120, 480), ("serve.reconcile", 600, 50),
                 ("serve.flush", 700, 80)]
    return _serialized([("/device:TPU:0", dev), ("/host:CPU",
                                                 {"python": host})])


def _both(data: bytes):
    return (PT.read(data),
            ProfileData.from_serialized_xspace(data))


def test_read_matches_profile_data():
    for path in (OLD, NEW):
        pd = ProfileData.from_file(str(path))
        for mine, pl in zip(PT.read(path), pd.planes):
            assert mine.name == pl.name
            for ln in pl.lines:
                got, want = mine.lines[ln.name], list(ln.events)
                assert [e.name for e in got] == [e.name for e in want]
                # ProfileData truncates a start and a duration each to
                # whole nanoseconds
                assert all(abs(a.start_ns - b.start_ns) < 1
                           and abs(a.end_ns - b.end_ns) < 2
                           for a, b in zip(got, want))


def test_gaps_take_the_innermost_span():
    planes, pd = _both(_trace())
    r = TR.reduce_trace(pd)
    spans = PT.program_spans(planes) + TR.host_spans(
        pd, {"engine.step", "bench.deliver"})
    gaps = PT.label_gaps(r.chips[0].intervals, r.t0, r.t1, spans)
    # idle: 0..100 (admit 20..60 and plan 60..80 inside serve.step,
    # engine.step alone before 10: admit covers most), 500..600 (wait),
    # 700..720 (flush), 730..1000 (flush to 780, deliver 800..1000)
    assert sorted((g, round(s * 1e9)) for g, s in gaps) == [
        ("bench.deliver", 270), ("serve.admit", 100),
        ("serve.flush", 20), ("serve.wait", 100)]
    # trace_reduce labels every one of them engine.step or deliver
    assert {g for g, _ in r.gaps} == {"engine.step", "bench.deliver"}


def test_window_and_idle_share_unchanged_by_program_spans():
    with_spans = TR.reduce_trace(_both(_trace())[1], lead_s=50e-9)
    without = TR.reduce_trace(_both(_trace(False))[1], lead_s=50e-9)
    assert (with_spans.t0, with_spans.t1) == (without.t0, without.t1)
    assert with_spans.busy_s == without.busy_s
    assert with_spans.gaps == without.gaps
    # over the benchmark's spans alone the labels are trace_reduce's
    planes, pd = _both(_trace(False))
    spans = TR.host_spans(pd, {"engine.step", "bench.deliver"})
    r = without
    assert PT.label_gaps(r.chips[0].intervals, r.t0, r.t1, spans) == \
        r.gaps


def test_program_times_from_xla_modules():
    planes, pd = _both(_trace())
    assert PT.program_ms(planes, "jit_packed_step", 0, 1000) == \
        pytest.approx(400e-6)
    assert PT.program_ms(planes, "jit_decode_step", 0, 1000) == \
        pytest.approx(100e-6)
    # an execution that crosses the window's edge is not counted
    assert PT.program_ms(planes, "jit_packed_step", 200, 1000) is None
    assert PT.program_ms(planes, "jit_chunk_prefill_step", 0, 1000) is None


def test_scope_self_time():
    planes, _ = _both(_trace())
    # kv_gather: copy 100 ns inside the packed step, fusion 100 ns in
    # the decode step; the step programs ran 500 ns
    assert PT.scope_pct(planes, "kv_gather", 0, 1000) == pytest.approx(40)
    assert PT.scope_pct(planes, "attn_kernel", 0, 1000) == \
        pytest.approx(20)
    assert PT.scope_pct(planes, "kv_gather", 0, 200) == pytest.approx(50)
    ops = PT.scope_ops(planes, "kv_gather", 0, 1000)
    assert ops["while"] == pytest.approx((200e-9, 0.0))
    assert ops["copy"] == pytest.approx((100e-9, 100e-9))
    assert ops["fusion"] == pytest.approx((110e-9, 100e-9))
    # a scope is a whole segment of the path, before the op type
    assert not PT.in_scope({PT.SCOPE_STAT: "a/kv_gathers/b:"}, "kv_gather")
    assert PT.in_scope({PT.SCOPE_STAT: "a/kv_gather/b:Gather"}, "kv_gather")
    assert not PT.in_scope({PT.SCOPE_STAT: "a/b:kv_gather"}, "kv_gather")


def test_host_stall_share():
    planes, pd = _both(_trace())
    r = TR.reduce_trace(pd)
    # idle under a host phase: admit 20..60, plan 60..80, launch 80..100
    # and flush 700..720, 730..780 -> 150 of 1000 ns
    assert PT.host_stall_pct(planes, r.chips[0].intervals, r.t0, r.t1) \
        == pytest.approx(15.0)
    got = PT.reduce_program(planes, r)
    assert got["packed_ms"] == pytest.approx(400e-6)
    assert got["kv_gather_pct"] == pytest.approx(40)
    assert got["host_stall_pct"] == pytest.approx(15.0)


def test_nothing_read_without_the_programs_names():
    for data in (_trace(program_spans=False, named=False), OLD):
        planes = PT.read(data)
        pd = (ProfileData.from_file(str(data)) if isinstance(data, Path)
              else ProfileData.from_serialized_xspace(data))
        labels = ("bench.step",) if data == OLD else (
            "engine.step", "bench.deliver")
        r = TR.reduce_trace(pd, labels=labels)
        assert PT.reduce_program(planes, r) == {
            "packed_ms": None, "decode_ms": None, "kv_gather_pct": None,
            "host_stall_pct": None}


def test_queue_wait_reader():
    read = spec.reader("queue_wait_p90_ms")

    def view(states):
        served = [NS(due=float(i), stream=NS(state=st))
                  for i, st in enumerate(states)]
        return NS(due_in_window=lambda: served)
    waits = [NS(t_first_dispatch=i + 0.1 * (i + 1)) for i in range(10)]
    assert read(view(waits)) == pytest.approx(900.0)
    assert read(view(waits[:3] + [None])) is None       # not admitted
    no_state = [NS(due=0.0, stream=NS())]               # older program
    assert read(NS(due_in_window=lambda: no_state)) is None


def test_recorded_v5e_trace_with_spans():
    planes = PT.read(NEW)
    pd = ProfileData.from_file(str(NEW))
    r = TR.reduce_trace(pd, labels=("engine.step",))
    got = PT.reduce_program(planes, r)
    assert got["packed_ms"] is not None and 100 < got["packed_ms"] < 400
    assert 0 < got["kv_gather_pct"] < 100
    assert got["host_stall_pct"] is not None
    mods = {m for p in PT.device_planes(planes) for _, _, m in
            PT._modules(p)}
    assert "jit_packed_step" in mods and "jit_body_core" not in mods
    assert r.op_calls("flash_decode_stats") > 0
    names = {s[2] for s in PT.program_spans(planes)}
    assert {"serve.step", "serve.launch", "serve.wait"} <= names
    spans = PT.program_spans(planes) + TR.host_spans(pd, {"engine.step"})
    gaps = PT.label_gaps(r.chips[0].intervals, r.t0, r.t1, spans)
    assert gaps and all(g.startswith("serve.") for g, _ in gaps)
