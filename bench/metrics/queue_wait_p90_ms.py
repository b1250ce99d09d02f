"""90th percentile (nearest rank), over the requests due in the window,
of the time from a request's due arrival to the dispatch of its first
prompt token (``RequestState.t_first_dispatch``, read through its
stream), in milliseconds: the wait for a slot and for prefill budget,
before its prefill ticks.  None where the program keeps no such
time."""
from bench.view import percentile


def read(v):
    waits = []
    for r in v.due_in_window():
        st = getattr(r.stream, "state", None)
        t = getattr(st, "t_first_dispatch", None)
        if t is None:
            return None
        waits.append(t - r.due)
    return 1e3 * percentile(waits, 90) if waits else None
