"""Host spans of the program: a bounded in-memory ring, also shown to the
profiler.

``span(name)`` times a block of host code.  It opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiled run shows
the span on the host plane, on the clock of the device ops; and it appends
``(name, start_ns, dur_ns, parent)`` to a ring of the last ``RING_SIZE``
spans, timed by ``time.time_ns()`` (the profiler's host clock).  ``parent``
is the name of the span open around it on the same thread, or None.
``snapshot()`` returns the ring in the order the spans ended, and
``reset()`` clears it.

No profiler session needs to be open: then a span costs two clock reads,
an annotation that records nothing, and one append.  Nothing here enters
jitted code.
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

import jax

RING_SIZE = 4096


class _OpenSpans(threading.local):
    def __init__(self):
        self.names = []


class Recorder:
    """A ring of finished spans, with the names of open spans per thread."""

    def __init__(self, size: int = RING_SIZE):
        self._ring = collections.deque(maxlen=size)
        self._open = _OpenSpans()

    @contextlib.contextmanager
    def span(self, name: str):
        names = self._open.names
        parent = names[-1] if names else None
        names.append(name)
        start = time.time_ns()
        try:
            with jax.profiler.TraceAnnotation(name):
                yield
        finally:
            self._ring.append((name, start, time.time_ns() - start, parent))
            names.pop()

    def snapshot(self) -> list:
        return list(self._ring)

    def reset(self):
        self._ring.clear()


RECORDER = Recorder()
span = RECORDER.span
snapshot = RECORDER.snapshot
reset = RECORDER.reset
