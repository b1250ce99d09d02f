"""Distributed equivalence, via subprocess runners so the forced
host-device count never leaks into this process (unit tests and benches
must see the single real CPU device)."""
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(__file__)


def run_child(script, timeout=1200):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    r = subprocess.run([sys.executable, os.path.join(HERE, script)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    sys.stdout.write(r.stdout[-4000:])
    sys.stderr.write(r.stderr[-4000:])
    return r.returncode


@pytest.mark.slow
def test_sharded_train_equivalence():
    """shard_map PRISM/Voltage/SSM/MoE train step over 8 host devices
    == single-device simulated protocol (loss AND gradients)."""
    assert run_child("shard_equiv_runner.py") == 0


@pytest.mark.slow
def test_sharded_serve_equivalence():
    """prefill + incremental decode over 8 host devices == full forward."""
    assert run_child("serve_smoke_runner.py") == 0


@pytest.mark.slow
def test_engine_continuous_batching_equivalence():
    """6 staggered requests through a 4-slot sharded engine produce the
    same tokens as sequential serving, in exact AND prism modes."""
    assert run_child("engine_equiv_runner.py") == 0


@pytest.mark.slow
def test_roofline_collective_parser():
    """collective_bytes() parses a real compiled HLO and finds the PRISM
    all-gather; PRISM moves fewer collective bytes than Voltage on the
    same (model, mesh) — the paper's central claim, at HLO level."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, os.path.join(os.getcwd(), "src"))
import jax, jax.numpy as jnp
from repro.core.protocol import PrismConfig
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import adamw_init
from repro.runtime.train import make_train_step, TrainHParams
from repro.launch.roofline import collective_bytes
from repro.launch.mesh import make_mesh

cfg = ModelConfig(name="t", arch_type="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128,
                  vocab_size=64, pos="rope")
mesh = make_mesh((2, 4), ("data", "model"))
key = jax.random.PRNGKey(0)
params = T.init(cfg, key)
hp = TrainHParams(remat=False, loss_chunks=2)
out = {}
for mode, cr in (("prism", 8.0), ("voltage", 1.0)):
    prism = PrismConfig(P=4, cr=cr, mode=mode)
    step, *_ = make_train_step(cfg, mesh, params, prism, hp)
    opt = jax.eval_shape(adamw_init, params)
    import jax as j
    psh = j.eval_shape(lambda: params)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    comp = step.lower(psh, opt, batch).compile()
    out[mode] = collective_bytes(comp.as_text())
print("prism", out["prism"]["total"], "voltage", out["voltage"]["total"])
assert out["prism"]["all-gather"] > 0
assert out["prism"]["total"] < out["voltage"]["total"], out
print("OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(HERE, "..", "src")
    r = subprocess.run([sys.executable, "-c", code],
                       capture_output=True, text=True, timeout=1200,
                       env=env, cwd=os.path.join(HERE, ".."))
    sys.stdout.write(r.stdout[-2000:])
    sys.stderr.write(r.stderr[-2000:])
    assert r.returncode == 0 and "OK" in r.stdout
