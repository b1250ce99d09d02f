"""Seconds from process start to the first measured second: start-up,
weights, programs from the compile cache, warm-up and the pre-roll of
traffic that brings the cell to its steady state."""


def read(v):
    return v.setup_s
