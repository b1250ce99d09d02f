"""Host spans of the serving loop.

``span(stats, name)`` times one phase of the loop on the host clock
(two ``time.perf_counter`` reads) and adds the seconds to
``stats.phase_s[name]``, a counter that is always on.  The same span
opens a ``jax.profiler.TraceAnnotation`` of that name, which records
only while a profiler session is active (``jax.profiler.trace`` or
``start_trace``), so the phases appear in a device trace on the host
plane, on the device's clock, with no switch of their own.
``step_span(n)`` marks one whole loop iteration as the profiler's
step ``n`` (``serve.step``).

The phases, nested as the loop runs them (docs/serving.md):
``serve.admit`` (releasing due arrivals, admission; ``serve.spill``
around each preemption gather), ``serve.plan`` (the tick's plan and
host vectors), ``serve.launch`` (host-to-device copies, the program
call, the result pack), ``serve.wait`` (the one blocking
``device_get``), ``serve.reconcile`` (advancing tokens) and
``serve.flush`` (delivering them to their streams).
"""
from __future__ import annotations

import time
from contextlib import contextmanager

import jax


@contextmanager
def span(stats, name: str):
    """Time the block into ``stats.phase_s[name]``; a profiler session
    also records it as a host span ``name``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        stats.phase_s[name] = (stats.phase_s.get(name, 0.0)
                               + time.perf_counter() - t0)


def step_span(n: int):
    """One loop iteration as the profiler's step ``n``."""
    return jax.profiler.StepTraceAnnotation("serve.step", step_num=n)
