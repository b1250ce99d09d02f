"""Continuous-batching engine demo: six requests with staggered
arrivals share four decode slots over a (2 data x 4 model) host mesh —
late arrivals are prefilled and spliced into slots freed by earlier
evictions, while the surviving streams keep decoding.  With the
default token-packed mode, each engine tick with any prefill work runs
ONE compiled program over a flat mixed batch of decode + prompt tokens
(watch the 'packed' step kinds below).

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python examples/serve_engine.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np


def main():
    import jax
    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.runtime.serve import ServeHParams
    from repro.serving import EngineConfig, SamplingParams, ServingEngine
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 8:
        print("set XLA_FLAGS=--xla_force_host_platform_device_count=8")
        sys.exit(1)
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_config("gpt2-small").reduced()
    params = T.init(cfg, jax.random.PRNGKey(0))

    # EngineConfig is the one construction path: paged page-table cache
    # and (in exact mode) shared-prefix reuse are on by default
    eng = ServingEngine(cfg, mesh, params, EngineConfig(
        n_slots=4, prefill_len=32, max_cache=48,
        hp=ServeHParams(decode_mode="exact", ssm_chunk=8)))

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=int(rng.integers(8, 33))).tolist()
               for _ in range(6)]
    # first four arrive immediately and fill the pool ...
    for p in prompts[:4]:
        eng.submit(p, max_new_tokens=10,
                   sampling=SamplingParams())        # greedy
    for _ in range(5):
        print(f"[demo] step -> {eng.step()}")
    # ... two more arrive mid-flight; they must wait for evictions
    for p in prompts[4:]:
        eng.submit(p, max_new_tokens=10)
    out = eng.run()

    for rid, toks in out.items():
        print(f"[demo] request {rid} ({len(prompts[rid])} prompt tokens) "
              f"-> {toks}")
    for k, v in eng.stats.summary().items():
        print(f"[demo] {k:22s} {v:.4f}" if isinstance(v, float)
              else f"[demo] {k:22s} {v}")
    for k, v in eng.kv_cache.stats().items():
        print(f"[demo] kv/{k:19s} {v}")


if __name__ == "__main__":
    main()
