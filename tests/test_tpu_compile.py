"""The main-path Pallas kernels compile for a TPU v5e at gpt2-small
widths (12 heads, head_dim 64, d_model 768).

Nothing runs: each kernel is lowered and compiled for a described
``v5e:2x2`` topology, which is what the chip's compiler would accept or
refuse (block-shape tiling, VMEM limits).  Interpret mode, which every
other kernel test uses, checks neither.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and under
multi-worker pytest only the worker that runs this file may do so.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import flash_decode_stats
from repro.kernels.ops import prism_attention_op
from repro.kernels.segment_means import segment_means_op

HQ = HKV = 12          # gpt2-small heads (no GQA)
HD = 64
D_MODEL = 768


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_for_chip(fn, *args):
    """Lower and compile for the described chip.  The kernels' own
    platform check sees this host's CPU, so callers pass
    ``interpret=False`` to get the compiled kernel."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# B = 8 engine slots (decode tick) and B = 72, the packed tick's token
# budget (8 slots + 64-token chunk), over a 1024-column cache row; the
# means columns are prism decode's 256 Segment-Means rows at CR 4.
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("means", [False, True], ids=["exact", "prism"])
@pytest.mark.parametrize("batch", [8, 72])
def test_flash_decode_compiles(one_chip, batch, means, dtype):
    m_loc, m_means = 1024, 256
    s = lambda shape, dt=dtype: _spec(one_chip, shape, dt)   # noqa: E731
    args = [s((batch, 1, HQ, HD)), s((batch, m_loc, HKV, HD)),
            s((batch, m_loc, HKV, HD)), s((batch, m_loc), jnp.bool_)]
    if means:
        args += [s((batch, m_means), jnp.float32),
                 s((batch, m_means, HKV, HD)), s((batch, m_means, HKV, HD))]
    _compile_for_chip(
        lambda *a: flash_decode_stats(*a, scale=HD ** -0.5,
                                      interpret=False), *args)


def test_flash_decode_chunk_folded_heads_compile(one_chip):
    """The chunk-prefill path folds C=64 queries into the head axis:
    B = 8 slots with 64·12 query heads against 12 KV heads."""
    b, c, m_loc = 8, 64, 1024
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)  # noqa
    _compile_for_chip(
        lambda *a: flash_decode_stats(*a, scale=HD ** -0.5,
                                      interpret=False),
        s((b, 1, c * HQ, HD)), s((b, m_loc, HKV, HD)),
        s((b, m_loc, HKV, HD)), s((b, m_loc), jnp.bool_))


# prefill at one chip's 512-token prompt, and one shard's view on a
# 1x4 mesh: 128 local rows + 3 peers' 32 Segment-Means columns (CR 4)
@pytest.mark.parametrize("nq,m,dtype", [
    (512, 512, jnp.float32), (512, 512, jnp.bfloat16),
    (128, 128 + 3 * 32, jnp.float32)], ids=["n512-f32", "n512-bf16",
                                             "p4-shard-f32"])
def test_prism_attention_compiles(one_chip, nq, m, dtype):
    s = lambda shape, dt=dtype: _spec(one_chip, shape, dt)   # noqa: E731
    i32 = lambda n: s((n,), jnp.int32)                       # noqa: E731
    _compile_for_chip(
        lambda *a: prism_attention_op(*a, causal=True, interpret=False),
        s((1, nq, HQ, HD)), s((1, m, HKV, HD)), s((1, m, HKV, HD)),
        s((m,), jnp.float32), i32(m), i32(m), i32(nq))


# L=32 means of a 128-row shard (seg 4) and L=24 of 72 rows (seg 3, a
# segment height that is no multiple of the 8-row sublane tile)
@pytest.mark.parametrize("n,L", [(128, 32), (72, 24)])
def test_segment_means_compiles(one_chip, n, L):
    _compile_for_chip(
        lambda x: segment_means_op(x, L=L, interpret=False),
        _spec(one_chip, (2, n, D_MODEL), jnp.float32))
