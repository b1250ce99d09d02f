"""Output tokens delivered to their streams in the window, over the
window's seconds.

Reported only where the offered load exceeds what the engine serves (a
closed loop, or an open loop above the knee): below the knee it reads
the offered rate shifted by the engine's lag."""


def read(v):
    w = v.window
    return len(v.tokens_between(w.t0, w.t1)) / v.seconds
