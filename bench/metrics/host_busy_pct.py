"""Share of the window the streaming front-end spent in its own host
bookkeeping (``EngineStats.host_busy_s``), in percent."""


def read(v):
    return 100.0 * v.stat("host_busy_s") / v.seconds
