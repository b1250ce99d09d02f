"""Least time the attention work of the traced window needs on this
chip, over the device time of the flash-decode kernel
(``flash_decode_stats``) in that window, in percent.

Work counted (``bench/flops.py``): every decode position after a
request's first token attends to its prefix, reading its keys and
values once; a request whose first token came in the traced window
counts its prompt's causal attention and reads its prompt's keys and
values once (a lower bound: its prefill ticks read its growing prefix
once each).  Memory sets the bound here: the bytes over HBM bandwidth
exceed the operations over the bf16 peak at every context length the
cells use."""
from bench import flops as F

KERNEL = "flash_decode_stats"


def read(v):
    if v.trace is None:
        return None
    kern = v.trace.op_seconds(KERNEL)
    if kern <= 0:
        return None
    a, b = v.trace_span()
    s = v.shape
    fl = by = 0
    for r, j in v.tokens_between(a, b):
        p = len(r.spec.prompt)
        if j == 0:
            fl += s.layers * sum(F.attention_flops(s, q + 1)
                                 for q in range(p))
            by += F.kv_bytes(s, p)
        else:
            pos = p + j - 1
            fl += s.layers * F.attention_flops(s, pos)
            by += F.kv_bytes(s, pos)
    least, _ = F.roofline_s(fl, by, v.peaks["bf16_flop_per_s"],
                            v.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (kern * v.chips) if least else None
