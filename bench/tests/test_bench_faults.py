"""A run with its timed path broken underneath comes out not correct,
and the control (the reference at a lower precision in the program's
place) fails the limit; a sound run comes out correct.  Everything but
the look for a chip runs, at a tiny size on the CPU."""
import argparse
import json

import jax
import jax.numpy as jnp
import pytest

from bench import control, run, spec
from bench.tests import tiny


def _tiny_run(seed=2**31 + 21):
    bm = json.loads(json.dumps(spec.benchmark()))
    for m in bm["end_to_end"] + bm["per_layer"]:
        m.pop("workloads", None)
    args = argparse.Namespace(workload="tiny-chat", seed=seed, seconds=2.0,
                              trace=0)
    return run.run_cell(args, tiny.cell(), tiny.GPT2, tiny.MIX, bm,
                        jax.devices(), run.peaks_for("TPU v5 lite"))


def _alter_tokens(monkeypatch):
    """Every token the device samples is replaced by its neighbour."""
    import repro.serving.streaming as S
    real = S.make_result_pack

    def broken(n_slots):
        pack, merge = real(n_slots)

        @jax.jit
        def pack2(logits, row_slot, is_decode, lengths):
            data = pack(logits, row_slot, is_decode, lengths)
            tok = (data[:, 0] + 1) % logits.shape[-1]
            return data.at[:, 0].set(tok.astype(jnp.int32))
        return pack2, merge
    monkeypatch.setattr(S, "make_result_pack", broken)


def _unwritten_cache(monkeypatch):
    """The programs return the KV pool as they got it: no step writes
    its keys and values."""
    import repro.runtime.serve as R
    monkeypatch.setattr(R, "_write_pool",
                        lambda pool, rows, page, poff, ok: pool)


def test_sound_run_is_correct():
    out = _tiny_run()
    assert out["correct"], out["check"]
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
    assert {"output_tok_s", "setup_s", "ttft_p50_ms", "itl_p95_ms"} <= set(
        out["metrics"])


@pytest.mark.parametrize("fault", [_alter_tokens, _unwritten_cache],
                         ids=["token_altered", "state_unchanged"])
def test_broken_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = _tiny_run()
    assert not out["correct"]
    gap = out["check"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_fails_the_limit():
    """bfloat16 products in the reference's place read a gap above the
    limit on each of three seeds, read at the served positions as a run
    reads the program, the program none (longer outputs than the other
    tests, so that the sample holds some hundreds of served tokens)."""
    mix = dict(tiny.MIX,
               body={"median": 24, "sigma": 0.3, "min": 16, "max": 40},
               output={"median": 40, "sigma": 0.3, "min": 24, "max": 56})
    cell = tiny.cell(check={"sample": 32, "max_logit_gap": 1e-3})
    lines = []
    s = control.measure(cell, tiny.GPT2, mix, [2**31 + 5, 77, 78], 3,
                        3.0, ("bf16",), emit=lines.append,
                        reference_with={"layer_norm_epsilon": 1e-6})
    limit = cell["check"]["max_logit_gap"]
    assert s["lower"] <= limit
    assert s["upper"]["bf16"] > limit
    for rec in map(json.loads, lines[:-1]):
        assert rec["program"]["correct"]
        assert not rec["control"]["bf16"]["correct"]
        assert rec["program_with"] == rec["program"]
