"""Model FLOPs the window's tokens required, over the window's seconds
times the chips times the chip's bf16 peak, in percent.

A request whose first token came in the window counts its prompt's
forward pass (less the prefix-cache tokens the window saved, at their
matrix-product cost), and every token after the first counts one
decode position; each token counts the output head once.  The rewind
that re-feeds a prompt's last token is recomputation and not counted."""
from bench import flops as F


def read(v):
    w, s = v.window, v.shape
    total = 0
    for r, j in v.tokens_between(w.t0, w.t1):
        p = len(r.spec.prompt)
        if j == 0:
            total += F.span_flops(s, 0, p)
        else:
            total += F.span_flops(s, p + j - 1, p + j)
        total += F.head_flops(s)
    total -= v.stat("prefix_tokens_saved") * s.layers * F.matmul_flops(s)
    peak = v.peaks["bf16_flop_per_s"] * v.chips * v.seconds
    return 100.0 * total / peak
