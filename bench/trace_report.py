"""Serve one cell's window and report where its time went, by the
program's own names.

    python3 bench/trace_report.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--keep <dir>]

The cell runs as ``bench/run.py`` runs it (same set-up, traffic and
window; no reference check) and one JSON line is printed: every
metric of ``BENCHMARK.json`` the cell reports, the seconds of each
``EngineStats.phase_s`` phase from the end of warm-up to the end of the
run, and, with ``--trace 1``, the readings of ``bench/program_trace.py``
over the traced window: step-program times, the ``kv_gather`` scope's
share and its operations, the host-stall share, the idle time by the
innermost host span, the window's time by that span, and the longest
idle gaps so labelled (label, seconds, offset into the window).  ``--keep``
copies the trace there.  Needs a TPU, as ``bench/run.py`` does.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench-out" / "trace-report"


def analyse(path, lead_s: float) -> tuple:
    """Reduce the trace at ``path`` as ``bench/run.py`` does; returns
    the ``Reduced`` and the program's readings over its window."""
    from bench import program_trace as PT
    from bench.trace_reduce import host_spans, load, reduce_trace
    pd = load(path)
    reduced = reduce_trace(pd, lead_s=lead_s)
    planes = PT.read(path)
    t0, t1 = reduced.t0, reduced.t1
    busy = reduced.chips[0].intervals
    spans = PT.program_spans(planes) + host_spans(
        pd, {"engine.step", "bench.submit", "bench.deliver", "bench.idle"})
    ops = PT.scope_ops(planes, "kv_gather", t0, t1)
    gaps = sorted(PT.labelled_gaps(busy, t0, t1, spans),
                  key=lambda g: g[0] - g[1])[:10]
    span_s: dict = {}
    for a, b, nm in PT.innermost(spans):
        if min(b, t1) > max(a, t0):
            span_s[nm] = span_s.get(nm, 0.0) + (min(b, t1) - max(a, t0)) / 1e9
    return reduced, {
        "program": PT.reduce_program(planes, reduced),
        "window_s": reduced.window_s, "busy_s": reduced.busy_s,
        "idle_by_span": PT.idle_by_span(busy, t0, t1, spans),
        "span_s": span_s,
        "gaps": [[nm, (b - a) / 1e9, (a - t0) / 1e9] for a, b, nm in gaps],
        "kv_gather_ops": {k: list(v) for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1][0])[:12]},
        "op_stats": sorted({k for p in PT.device_planes(planes)
                            for e in p.lines.get("XLA Ops", [])[:200]
                            for k in e.stats})}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import drive, spec
    from bench import flops as F
    from bench.generator import generate
    from bench.run import configure_jax, find_trace, log, peaks_for
    from bench.view import View
    from bench.weights import make_weights
    from repro.launch.mesh import make_mesh

    bm = spec.benchmark(ROOT)
    cell = spec.cell(args.workload)
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    d0 = jax.devices()[0]
    if d0.platform != "tpu":
        log(f"needs a TPU, JAX found {d0.platform}")
        return 1
    configure_jax(conf, ROOT / ".jax_cache")
    cfg = spec.model_config(conf)
    mesh = make_mesh(tuple(cell["mesh"]), ("data", "model"))
    weights = make_weights(cfg, mesh, args.seed)
    seng = drive.build_engine(cfg, mesh, weights, cell)
    drive.warm_up(seng, cfg.vocab_size, args.seed + 1)
    traffic = generate(mix, args.seed, cell["preroll_s"] + args.seconds,
                       cfg.vocab_size)
    stats = seng.engine.stats
    phase0 = dict(getattr(stats, "phase_s", {}))
    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    w = drive.run_window(seng, traffic, preroll_s=cell["preroll_s"],
                         seconds=args.seconds, trace_dir=trace_dir,
                         trace_s=float(cell["trace_s"]),
                         annotate=bool(args.trace))
    phase = {k: v - phase0.get(k, 0.0)
             for k, v in sorted(getattr(stats, "phase_s", {}).items())}
    del seng
    out = {"workload": args.workload, "seed": args.seed,
           "device": {"kind": d0.device_kind, "count": len(jax.devices())},
           "late_s": w.late_s, "phase_s": phase}
    reduced = None
    if args.trace:
        path = find_trace(TRACE_DIR)
        if args.keep:
            Path(args.keep).mkdir(parents=True, exist_ok=True)
            shutil.copy(path, Path(args.keep) / f"{args.workload}-"
                        f"{args.seed}.xplane.pb")
        reduced, found = analyse(path, drive.TRACE_LEAD_S)
        out.update(found)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    view = View(window=w, setup_s=0.0, shape=F.Shape.of(cfg),
                peaks=peaks_for(d0.device_kind), chips=int(cell["chips"]),
                trace=reduced)
    out["metrics"] = {}
    for traced in (False, True):
        for m in spec.metrics_for(bm, cell["name"], traced):
            if m["name"] == "setup_s":
                continue
            v = spec.reader(m["name"])(view)
            if v is not None:
                out["metrics"][m["name"]] = float(v)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
