"""95th percentile (nearest rank) of every gap between two consecutive
tokens of one request, both delivered in the window, in milliseconds."""
from bench.view import percentile


def read(v):
    w = v.window
    gaps = []
    for r in w.served:
        ts = r.times
        gaps.extend(b - a for a, b in zip(ts, ts[1:])
                    if w.t0 <= a and b < w.t1)
    return 1e3 * percentile(gaps, 95) if gaps else None
