"""Host spans of ``FLSimulator.step_round``, on the profiler's own clock.

``span(seconds, name)`` opens a ``jax.profiler.TraceAnnotation`` named
``repro.<name>``, so that under ``jax.profiler.trace`` the span lands in
the same ``.xplane.pb`` and on the same clock as the device's
operations, and adds the span's host seconds (``time.perf_counter``) to
``seconds[name]``. With no trace running the annotation is a no-op
object and the cost is two clock reads and a dict addition. A span
never syncs, copies or allocates device memory.

The names ``FLSimulator`` uses (``host_seconds``):

- ``round``: the whole ``step_round`` call, parent of the rest;
- ``assemble``: engine draw, schedule, working set, operators,
  ``_resolve_args``, forwarding indices;
- ``dispatch``: the jitted calls and the start of their D2H copies;
- ``page.wait``: blocking on a round's results for the store;
- ``page.commit``: writing them into the host store;
- ``page.stage``: gathering, filling and putting a round's page-in.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, Iterator

import jax

#: prefix of the program's host spans in a trace
PREFIX = "repro."


@contextlib.contextmanager
def span(seconds: Dict[str, float], name: str) -> Iterator[None]:
    """Annotate the block as ``repro.<name>`` and add its host seconds
    to ``seconds[name]``."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
    finally:
        seconds[name] = seconds.get(name, 0.0) + time.perf_counter() - t0
