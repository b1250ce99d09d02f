"""A tiny cell for CPU tests: gpt2-shaped and llama-shaped models of a
few thousand parameters, and short traffic."""
from __future__ import annotations

import copy

GPT2 = {
    "name": "tiny-gpt2", "arch": "gpt2-small", "reference": "gpt2",
    "reference_tokens": 128, "n_layer": 2, "n_embd": 64, "n_head": 2,
    "n_inner": 128, "vocab_size": 512, "n_positions": 256,
    "layer_norm_epsilon": 1e-6, "matmul_precision": "highest",
    "program": {"n_layers": 2, "d_model": 64, "n_heads": 2,
                "n_kv_heads": 2, "head_dim": 32, "d_ff": 128,
                "vocab_size": 512, "max_seq": 256},
}

LLAMA = {
    "name": "tiny-llama", "arch": "yi-6b", "reference": "llama",
    "reference_tokens": 128, "num_hidden_layers": 2, "hidden_size": 64,
    "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 384,
    "rope_theta": 5000000.0, "rms_norm_eps": 1e-6,
    "matmul_precision": "highest",
    "program": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
                "vocab_size": 384},
}

MIX = {
    "arrival": {"kind": "poisson", "rate_per_s": 4.0},
    "prefixes": {"count": 2, "tokens": 16, "zipf_s": 1.1},
    "body": {"median": 12, "sigma": 0.5, "min": 4, "max": 40},
    "output": {"median": 5, "sigma": 0.4, "min": 2, "max": 10},
    "block": 8,
}

CELL = {
    "name": "tiny-chat", "config": "tiny-gpt2", "traffic": "tiny",
    "chips": 1, "mesh": [1, 1], "decode_mode": "exact", "cr": None,
    "engine": {"n_slots": 4, "max_cache": 128, "prefill_len": 64},
    "preroll_s": 0.5, "trace_s": 0.5,
    "check": {"sample": 4, "max_logit_gap": 1e-3},
    "why": "tiny",
}


def cell(**kw) -> dict:
    c = copy.deepcopy(CELL)
    c.update(kw)
    return c
