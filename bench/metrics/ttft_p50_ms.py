"""Median over every request due in the window of the time from its
due arrival to its first token, in milliseconds."""
from bench.view import percentile


def read(v):
    xs = v.ttfts()
    return 1e3 * percentile(xs, 50) if xs else None
