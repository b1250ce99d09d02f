"""Mean share of decode slots in use over the window's ticks
(``EngineStats.occupancy``), in percent."""


def read(v):
    occ = v.window.occupancy
    return 100.0 * sum(occ) / len(occ) if occ else None
