"""The host span recorder (``repro.runtime.trace``): nesting, the ring's
bound, reset, and what a span costs with no profiler session open."""
import threading
import time

from repro.runtime import trace
from repro.runtime.trace import Recorder


def test_nested_spans_record_parent_and_interval():
    rec = Recorder()
    with rec.span('outer'):
        with rec.span('inner'):
            time.sleep(0.001)
        with rec.span('inner2'):
            pass
    spans = rec.snapshot()
    # the ring holds spans in the order they ended
    assert [(s[0], s[3]) for s in spans] == [
        ('inner', 'outer'), ('inner2', 'outer'), ('outer', None)]
    (_, i0, idur, _), (_, j0, jdur, _), (_, o0, odur, _) = spans
    assert o0 <= i0 and i0 + idur <= j0 and j0 + jdur <= o0 + odur
    assert idur >= 1_000_000


def test_span_closes_on_exception():
    rec = Recorder()
    try:
        with rec.span('outer'):
            with rec.span('raises'):
                raise ValueError('boom')
    except ValueError:
        pass
    with rec.span('after'):
        pass
    assert [(s[0], s[3]) for s in rec.snapshot()] == [
        ('raises', 'outer'), ('outer', None), ('after', None)]


def test_parent_is_per_thread():
    rec = Recorder()

    def worker():
        with rec.span('thread'):
            pass

    with rec.span('main'):
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert dict((s[0], s[3]) for s in rec.snapshot()) == {
        'thread': None, 'main': None}


def test_ring_is_bounded_and_keeps_the_newest():
    rec = Recorder(size=8)
    for i in range(20):
        with rec.span(f's{i}'):
            pass
    assert [s[0] for s in rec.snapshot()] == [f's{i}' for i in range(12, 20)]
    assert trace.RING_SIZE == 4096


def test_reset_clears_the_ring():
    rec = Recorder()
    with rec.span('a'):
        pass
    rec.reset()
    assert rec.snapshot() == []
    with rec.span('b'):
        pass
    assert [s[0] for s in rec.snapshot()] == ['b']


def test_span_costs_little_without_a_profiler_session():
    rec = Recorder()
    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        with rec.span('md.chunk'):
            pass
    per_span_us = (time.perf_counter() - t0) / n * 1e6
    assert len(rec.snapshot()) == trace.RING_SIZE
    assert per_span_us < 20.0, per_span_us
