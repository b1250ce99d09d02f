"""bench/trace_reduce.py on a hand-built trace whose answers are known,
and on a slice of a trace recorded on a TPU v5e: gpt2-small served by
the streaming engine (32 slots, 1024-column cache), 96 ms around one
decode-program execution, with the driving loop's ``bench.step`` host
spans; its device events are as the profiler wrote them."""
from pathlib import Path

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce as TR

FIXTURE = Path(__file__).parent / "fixtures" / "engine_v5e.xplane.pb"


def _xspace(planes: list) -> ProfileData:
    """planes: [(plane name, {line name: [(name, start_ns, dur_ns)]})]."""
    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names = sorted({e[0] for evs in lines.values() for e in evs})
        ids = {n: i + 1 for i, n in enumerate(names)}
        body = [f'id: {pid} name: "{pname}"']
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            ev = " ".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {int(a * 1000)}"
                f" duration_ps: {int(d * 1000)} }}" for n, a, d in evs)
            body.append(f'lines {{ id: {lid} name: "{lname}" '
                        f"timestamp_ns: 0 {ev} }}")
        for n, i in ids.items():
            body.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}')
        out.append("planes { " + " ".join(body) + " }")
    txt = "\n".join(out)
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(txt))


def _trace():
    dev0 = {"XLA Ops": [
        ("%while.3 = (f32[2]) while()", 100, 400),          # 100..500
        ("%flash_decode_stats.8 = (f32[1]) custom-call()", 150, 100),
        ("%fusion.2 = f32[1] fusion()", 300, 50),
        ("%all-reduce.1 = f32[1] all-reduce()", 600, 100),  # 600..700
        ("%fusion.9 = f32[1] fusion()", 650, 20),
        ("%flash_decode_stats.8 = (f32[1]) custom-call()", 800, 100)],
        "XLA Modules": [("jit_body_core(111)", 100, 400),
                        ("jit_body_core(222)", 600, 300)]}
    dev1 = {"XLA Ops": [("%fusion.1 = f32[1] fusion()", 100, 800)]}
    host = {"python": [("engine.step", 0, 550),
                       ("bench.deliver", 550, 200),
                       ("engine.step", 750, 250)]}
    return _xspace([("/device:TPU:0", dev0), ("/device:TPU:1", dev1),
                    ("/host:CPU", host)])


def test_op_names():
    assert TR.op_name("%flash_decode_stats.8 = (f32[96]) x") == \
        "flash_decode_stats"
    assert TR.op_name("%copy-start.42 = (f32[768]) y") == "copy-start"
    assert TR.op_name("%while = (s32[]) z") == "while"


def test_hand_built_trace():
    r = TR.reduce_trace(_trace(), lead_s=100e-9)
    # host spans cover 0..1000, the first 100 ns are the lead-in; the
    # chips recorded from 100 to 900, so 900..1000 is idle
    assert (r.t0, r.t1) == (100, 1000)
    assert r.window_s == pytest.approx(900e-9)
    c0, c1 = r.chips
    assert c0.intervals == [[100, 500], [600, 700], [800, 900]]
    assert c0.busy_ns == 600 and c1.busy_ns == 800
    assert r.busy_s == pytest.approx(700e-9)
    # self times: the while holds the kernel and a fusion
    assert c0.op_self_ns["while"] == 400 - 100 - 50
    assert c0.op_self_ns["flash_decode_stats"] == 200
    assert c0.op_calls["flash_decode_stats"] == 2
    assert r.op_seconds("flash_decode_stats") == pytest.approx(100e-9)
    # the all-reduce runs 600..700 with compute only 650..670
    assert c0.collective_exposed_ns == 80
    # idle on chip 0: 500..600 (step 500..550 and deliver 550..600: a
    # tie goes to the earlier span), 700..800 (deliver 700..750, step
    # 750..800: the same) and 900..1000 (step)
    assert sorted((g, round(s * 1e9)) for g, s in r.gaps) == [
        ("bench.deliver", 100), ("engine.step", 100), ("engine.step", 100)]


def test_window_is_given_or_from_host_spans():
    r = TR.reduce_trace(_trace(), window=(100, 500))
    assert r.chips[0].busy_ns == 400 and r.gaps == []
    r = TR.reduce_trace(_trace())
    assert (r.t0, r.t1) == (0, 1000)
    assert r.chips[0].busy_ns == 600 and len(r.gaps) == 4
    with pytest.raises(ValueError, match="host spans"):
        TR.reduce_trace(_trace(), labels=("nothing",))


def test_recorded_v5e_trace():
    r = TR.reduce_trace(TR.load(FIXTURE), labels=("bench.step",),
                        lead_s=0.002)
    assert len(r.chips) == 1
    assert r.window_s == pytest.approx(0.0937, abs=1e-3)
    assert 0.9 * r.window_s < r.busy_s <= r.window_s
    names = [n for n, _ in r.top_ops(10)]
    assert "flash_decode_stats" in names and "copy" in names
    # one decode-program execution: the kernel once per layer (12)
    assert r.op_calls("flash_decode_stats") == 12
    assert r.gaps and {g for g, _ in r.gaps} == {"bench.step"}
    # the ten longest of the idle stretches
    assert len(r.gaps) == 10
    assert sum(s for _, s in r.gaps) <= r.window_s - r.busy_s + 1e-12
