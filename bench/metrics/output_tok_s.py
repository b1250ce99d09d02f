"""Output tokens delivered to their streams in the window, over the
window's seconds."""


def read(v):
    w = v.window
    return len(v.tokens_between(w.t0, w.t1)) / v.seconds
