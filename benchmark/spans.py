"""Host spans the harness records around its own calls into each layer.

Each span is kept in memory as (name, start, end) on the host's
``perf_counter`` clock.  In a traced run it is also written into the
profiler's trace as a ``TraceAnnotation`` of the same name, so the
trace reduction can put it on the device's clock.
"""

import contextlib
import time

import jax


class Spans:
    def __init__(self, traced: bool = False):
        self.traced = traced
        self.items = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        note = (jax.profiler.TraceAnnotation(name) if self.traced
                else contextlib.nullcontext())
        t0 = time.perf_counter()
        try:
            with note:
                yield
        finally:
            self.items.append((name, t0, time.perf_counter()))
