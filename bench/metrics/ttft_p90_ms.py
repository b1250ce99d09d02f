"""90th percentile (nearest rank) over every request due in the window
of the time from its due arrival to its first token, in milliseconds.
A request that never got a first token counts beyond all others."""
from bench.view import percentile


def read(v):
    xs = v.ttfts()
    return 1e3 * percentile(xs, 90) if xs else None
