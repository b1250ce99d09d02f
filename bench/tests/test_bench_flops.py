"""bench/flops.py against counts worked out by hand."""
from bench import flops as F

GPT2 = F.Shape(layers=12, d_model=768, n_heads=12, n_kv_heads=12,
               head_dim=64, d_ff=3072, vocab=50257, gated=False)
YI8 = F.Shape(layers=8, d_model=4096, n_heads=32, n_kv_heads=4,
              head_dim=128, d_ff=11008, vocab=64000, gated=True)


def test_matmul_flops_per_layer():
    # gpt2: Q, K, V, O are 768x768 each; MLP 768x3072 twice
    assert F.matmul_flops(GPT2) == 2 * (4 * 768 * 768 + 2 * 768 * 3072)
    assert F.matmul_flops(GPT2) == 14_155_776
    # yi: Q and O 4096x4096, K and V 4096x512; SwiGLU 3 x 4096x11008
    assert F.matmul_flops(YI8) == 2 * (2 * 4096 * 4096 + 2 * 4096 * 512
                                       + 3 * 4096 * 11008)
    assert F.matmul_flops(YI8) == 346_030_080


def test_head_and_attention():
    assert F.head_flops(GPT2) == 2 * 768 * 50257
    assert F.head_flops(YI8) == 524_288_000
    assert F.attention_flops(YI8, 10) == 4 * 32 * 128 * 10
    assert F.attention_flops(GPT2, 1) == 3072


def test_span_and_token():
    # three positions 0, 1, 2 attend to 1, 2, 3 columns
    want = 12 * (3 * 14_155_776 + 3072 * (1 + 2 + 3))
    assert F.span_flops(GPT2, 0, 3) == want
    # a span splits into its parts
    assert (F.span_flops(YI8, 0, 100) ==
            F.span_flops(YI8, 0, 37) + F.span_flops(YI8, 37, 100))
    assert F.token_flops(YI8, 99) == (F.span_flops(YI8, 99, 100)
                                      + 524_288_000)
    assert F.token_flops(YI8, 99, sampled=False) == F.span_flops(
        YI8, 99, 100)


def test_kv_bytes():
    # float32 K and V: yi 4 heads x 128, gpt2 12 heads x 64, per layer
    assert F.kv_bytes(YI8, 1) == 8 * 4 * 128 * 2 * 4
    assert F.kv_bytes(GPT2, 1024) == 12 * 1024 * 12 * 64 * 2 * 4


def test_roofline_names_its_bound():
    t, bound = F.roofline_s(197e12, 1.0, 197e12, 819e9)
    assert (t, bound) == (1.0, "compute")
    t, bound = F.roofline_s(1.0, 819e9, 197e12, 819e9)
    assert (t, bound) == (1.0, "memory")


def test_shape_of_program_config():
    from repro.configs import get_config
    assert F.Shape.of(get_config("gpt2-small")) == GPT2
    from dataclasses import replace
    assert F.Shape.of(replace(get_config("yi-6b"), n_layers=8)) == YI8
