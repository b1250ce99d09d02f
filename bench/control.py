"""Read the two readings a cell's correctness limit is set from.

    python3 bench/control.py --workload <cell> --seeds 101-106 \\
        --control 3 --seconds 20 [--modes high,bf16] \\
        [--reference-with rms_norm_eps=1e-5]

For each seed the cell is served as a run serves it (a short window at
the cell's own load) and the program's number is read as a run reads
it (``bench/check.py``'s ``judge``): the widest gap below the
reference's best logit of a served token.  For the first ``--control``
seeds the control is read through the same ``judge``, on the same
sample: the reference itself put in the program's place at each lower
precision of ``--modes``.  ``--reference-with`` reads the program's
tokens once more against a reference whose configuration has those
keys replaced (a published value the program departs from).  One JSON
line per seed, then a summary line with the lower reading (the largest
program reading) and, per mode, the upper one (the smallest control
reading).  All seeds run in one process, so the programs compile once.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seeds_arg(text: str) -> list:
    a, _, b = text.partition("-")
    return list(range(int(a), int(b or a) + 1))


def _number(verdict: dict) -> dict:
    return {"gap": verdict["numbers"]["max_logit_gap"]["value"],
            "correct": verdict["correct"]}


def measure(cell: dict, conf: dict, mix: dict, seeds: list, n_control: int,
            seconds: float, modes: tuple, cache_dir=None, emit=print,
            reference_with=None) -> dict:
    from bench import check, run, spec
    ref = spec.reference(conf)
    rows = []
    for i, seed in enumerate(seeds):
        sv = run.serve(cell, conf, mix, seed, seconds, cache_dir)

        def judge(c=conf, control=None):
            return _number(check.judge(ref, c, sv.weights, sv.window,
                                       cell["check"], seed, control))
        v = check.judge(ref, conf, sv.weights, sv.window, cell["check"], seed)
        rec = {"seed": seed, "requests": v["numbers"]["sample_requests"],
               "tokens": v["numbers"]["sample_tokens"],
               "program": _number(v)}
        if reference_with:
            rec["program_with"] = judge(dict(conf, **reference_with))
        if i < n_control:
            t = time.monotonic()
            rec["control"] = {m: judge(control=m) for m in modes}
            rec["control_s"] = time.monotonic() - t
        emit(json.dumps(rec))
        rows.append(rec)
        del sv
        gc.collect()
    prog = [r["program"]["gap"] for r in rows
            if r["program"]["gap"] is not None]
    summary = {"lower": max(prog) if prog else None, "upper": {
        m: min(r["control"][m]["gap"] for r in rows if "control" in r)
        for m in modes} if n_control else {}}
    emit(json.dumps({"summary": summary}))
    return summary


def overrides(text: str) -> dict:
    out = {}
    for item in filter(None, text.split(",")):
        k, _, v = item.partition("=")
        out[k] = float(v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds_arg, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--modes", default="high,bf16")
    ap.add_argument("--reference-with", type=overrides, default={})
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from bench import spec
    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 1
    cell = spec.cell(args.workload)
    conf = spec.config(cell["config"])
    measure(cell, conf, spec.traffic(cell["traffic"]), args.seeds,
            args.control, args.seconds, tuple(args.modes.split(",")),
            ROOT / ".jax_cache", reference_with=args.reference_with)
    return 0


if __name__ == "__main__":
    sys.exit(main())
