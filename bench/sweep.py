"""Find an open-loop cell's knee: the highest offered rate at which the
backlog does not grow over the window.

    python3 bench/sweep.py --workload <cell> --rates 0.8,1.0,1.2 \\
        --seconds 30 --seed 7

Each rate serves the cell with its mix's ``rate_per_s`` replaced, in
one process, and prints one JSON line: the backlog (requests due and
still without a first token) at the window's start and end, the
tokens offered and delivered per second, and the time to first token.
The knee found is written into the cell's traffic file by hand, as a
number; the benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def backlog(window, t: float) -> int:
    return sum(1 for r in window.served
               if r.due <= t and (not r.times or r.times[0] > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import run, spec
    from bench.view import View, percentile
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    cell = spec.cell(args.workload)
    conf = spec.config(cell["config"])
    mix = spec.traffic(cell["traffic"])
    for rate in [float(x) for x in args.rates.split(",")]:
        m = dict(mix, arrival=dict(mix["arrival"], rate_per_s=rate))
        sv = run.serve(cell, conf, m, args.seed, args.seconds,
                       ROOT / ".jax_cache")
        w = sv.window
        v = View(window=w, setup_s=sv.setup_s, shape=None, peaks={},
                 chips=1)
        due = v.due_in_window()
        ttft = v.ttfts()
        print(json.dumps({
            "rate": rate, "due": len(due),
            "backlog_start": backlog(w, w.t0), "backlog_end": backlog(w, w.t1),
            "offered_tok_s": sum(r.spec.max_new for r in due) / v.seconds,
            "output_tok_s": len(v.tokens_between(w.t0, w.t1)) / v.seconds,
            "ttft_p50_ms": 1e3 * percentile(ttft, 50),
            "ttft_p90_ms": 1e3 * percentile(ttft, 90),
            "packed_ticks": w.stats1["packed_ticks"] - w.stats0["packed_ticks"],
            "decode_ticks": w.stats1["decode_steps"] - w.stats0["decode_steps"]}),
            flush=True)
        del sv
    return 0


if __name__ == "__main__":
    sys.exit(main())
