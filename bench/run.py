"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's files (see ``bench/spec.py``) say what to build: the model
from its configuration, random weights from the seed made on the
device, the serving engine with the cell's settings, and the traffic.
Set-up compiles or loads every program the cell uses and runs the
traffic's pre-roll; then the window measures for ``--seconds``.  With
``--trace 1`` the window's last ``trace_s`` seconds (the cell file's)
are profiled and the per-layer metrics are reported instead of the
end-to-end ones.

The run fails, printing no result, when JAX finds no TPU, fewer chips
than the cell asks for, or a kernel backend other than the compiled
Pallas kernels.  After the window a sample of finished requests is
checked against the plain reference (``bench/check.py``).  The compile
cache is ``.jax_cache`` in this checkout.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / "bench-out" / "trace"


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def configure_jax(conf: dict, cache_dir) -> None:
    """float32 products at the configuration's precision, set process
    wide (the streaming loop's ticks may run on another thread), and
    the persistent compile cache, every program in it, at
    ``cache_dir``."""
    import jax
    jax.config.update("jax_default_matmul_precision",
                      conf["matmul_precision"])
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_trace(root: Path) -> Path:
    found = sorted(root.glob("**/*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {root}")
    return found[-1]


@dataclass
class Served:
    cfg: object                        # the program's model config
    weights: object                    # the benchmark's weights
    window: object                     # drive.Window
    setup_s: float
    memory_peak_bytes: int
    totals: dict                       # engine counters at the end


def serve(cell: dict, conf: dict, mix: dict, seed: int, seconds: float,
          cache_dir=None, trace_dir=None) -> Served:
    """Build the cell from ``seed``, warm it up, run the pre-roll and
    the window, and free the engine; the weights are kept for the
    reference."""
    import jax
    from bench import drive, spec
    from bench.generator import generate
    from bench.weights import make_weights
    from repro.launch.mesh import make_mesh

    configure_jax(conf, cache_dir)
    watch = drive.CompileWatch()
    cfg = spec.model_config(conf)
    mesh = make_mesh(tuple(cell["mesh"]), ("data", "model"))
    weights = make_weights(cfg, mesh, seed)
    jax.block_until_ready(weights)
    log(f"weights ready at {time.monotonic() - T_START:.1f} s")
    seng = drive.build_engine(cfg, mesh, weights, cell)
    drive.warm_up(seng, cfg.vocab_size, seed + 1)
    log(f"programs warm at {time.monotonic() - T_START:.1f} s "
        f"({watch.n} compiles or cache loads so far)")
    traffic = generate(mix, seed, cell["preroll_s"] + seconds,
                       cfg.vocab_size)
    w = drive.run_window(seng, traffic, preroll_s=cell["preroll_s"],
                         seconds=seconds,
                         watch=watch, trace_dir=trace_dir,
                         trace_s=float(cell["trace_s"]),
                         annotate=trace_dir is not None)
    # the CPU backend keeps no memory statistics (tests only)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in mesh.devices.flat)
    totals = drive.snapshot(seng.engine.stats)
    del seng
    gc.collect()
    log(f"compiles or cache loads inside the window: {w.compiles}; "
        f"program retraces inside the window: {w.traces}; submissions "
        f"ran up to {w.late_s * 1e3:.1f} ms late; engine totals {totals}")
    return Served(cfg=cfg, weights=weights, window=w,
                  setup_s=w.t0_mono - T_START, memory_peak_bytes=int(peak),
                  totals=totals)


def run_cell(args, cell: dict, conf: dict, mix: dict, bm: dict,
             devices: list, peaks: dict, cache_dir=None) -> dict:
    """Everything after the look for a chip: serve, check, read the
    metrics, and return the result object."""
    from bench import check, drive, spec
    from bench import flops as F
    from bench.trace_reduce import load, reduce_trace
    from bench.view import View

    trace_dir = None
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        trace_dir = str(TRACE_DIR)
    sv = serve(cell, conf, mix, args.seed, args.seconds, cache_dir,
               trace_dir)
    w = sv.window
    due = [r for r in w.served if w.t0 <= r.due < w.t1]
    failed = sum(1 for r in due if not r.times)
    log(f"window: {len(due)} requests due, {failed} without a first "
        f"token, {len(w.served)} submitted in all")
    t_ref = time.monotonic()
    verdict = check.judge(spec.reference(conf), conf, sv.weights, w,
                          cell["check"], args.seed)
    log(f"reference check took {time.monotonic() - t_ref:.1f} s")

    reduced = None
    if args.trace:
        reduced = reduce_trace(load(find_trace(TRACE_DIR)),
                               lead_s=drive.TRACE_LEAD_S)
        iv = reduced.chips[0].intervals or [[reduced.t1, reduced.t1]]
        log(f"traced window {reduced.window_s:.3f} s: device operations "
            f"from +{(iv[0][0] - reduced.t0) / 1e9:.3f} s to "
            f"-{(reduced.t1 - iv[-1][1]) / 1e9:.3f} s of it")
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    view = View(window=w, setup_s=sv.setup_s, shape=F.Shape.of(sv.cfg),
                peaks=peaks, chips=int(cell["chips"]), trace=reduced)
    metrics = {}
    for m in spec.metrics_for(bm, cell["name"], bool(args.trace)):
        value = spec.reader(m["name"])(view)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": sv.memory_peak_bytes}
    out = {"correct": bool(verdict["correct"] and failed == 0),
           "attempted": len(due), "failed": failed, "metrics": metrics,
           "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in reduced.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in reduced.gaps[:10]]}
    out["check"] = dict(verdict["numbers"],
                        failed_requests={"value": failed, "limit": 0})
    return out


def peaks_for(kind: str) -> dict:
    from bench import spec
    table = spec.read_json(spec.BENCH / "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}: add it to "
                       "bench/peaks.json with its source")
    return table[kind]


def main(argv=None) -> int:
    args = parse(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import spec
    bm = spec.benchmark(ROOT)
    cell = spec.cell(args.workload)
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    import jax
    from repro.kernels.dispatch import resolve_backend
    devices = jax.devices()
    d0 = devices[0]
    log(f"device {d0.platform} {d0.device_kind} x{len(devices)}; "
        f"cell {cell['name']} asks for {cell['chips']}")
    if d0.platform != "tpu":
        log(f"needs a TPU, JAX found {d0.platform}")
        return 1
    if len(devices) < int(cell["chips"]):
        log(f"needs {cell['chips']} chips, JAX found {len(devices)}")
        return 1
    if resolve_backend("auto") != "pallas":
        log("the kernel backend does not resolve to the Pallas kernels")
        return 1
    out = run_cell(args, cell, conf, mix, bm, devices,
                   peaks_for(d0.device_kind), ROOT / ".jax_cache")
    for k, v in out["check"].items():
        log(f"check {k}: " + (f"{v['value']} (limit {v['limit']})"
                               if isinstance(v, dict) else f"{v}"))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
