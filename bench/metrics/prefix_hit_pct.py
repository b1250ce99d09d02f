"""Share of the prompt tokens admitted in the window that the prefix
cache served (``prefix_tokens_saved``) rather than the packed ticks
prefilled (``packed_prefill_tokens``), in percent."""


def read(v):
    saved = v.stat("prefix_tokens_saved")
    total = saved + v.stat("packed_prefill_tokens")
    return 100.0 * saved / total if total else None
