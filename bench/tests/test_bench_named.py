"""The readers that find the program's work by name (``named.py``): the
three kernels by their instruction names, in either layout, and the MD
loop's host time per chunk from the program's spans.  On the recorded
chip traces, which predate the names, each kernel reader finds nothing."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402
import named  # noqa: E402

MS = 1_000_000
KERNEL_METRICS = {'snap_u_ms_per_eval': 'snap_u',
                  'snap_y_ms_per_eval': 'snap_y',
                  'snap_de_ms_per_eval': 'snap_fused_de'}


def named_trace(suffix):
    """Three force evaluations in a 100 ms window: U 2 ms, Y 20 ms and dE
    5 ms each, and 3 ms of glue each (a fusion and a copy); the device
    loop's while op encloses them all."""
    ops = [['%while.3 = (f32[2000,3]) while(...)', '', 0, 95 * MS]]
    for k in range(3):
        t = 30 * k * MS
        ops += [
            [f'%snap_u{suffix}.{k} = (f32[155,2048]{{1,0}}, f32[155,2048]'
             f'{{1,0}}) custom-call(f32[72,4,2048]{{2,1,0}} %p), '
             'custom_call_target="tpu_custom_call"', '', t, 2 * MS],
            [f'%snap_y{suffix}.{k} = (f32[155,2048]{{1,0}}, f32[155,2048]'
             '{1,0}) custom-call(...), custom_call_target="tpu_custom_call"',
             '', t + 2 * MS, 20 * MS],
            [f'%snap_fused_de{suffix}.{k} = f32[72,4,2048]{{2,1,0}} '
             'custom-call(...), custom_call_target="tpu_custom_call"', '',
             t + 22 * MS, 5 * MS],
            ['%fusion.291 = f32[16000,3]{1,0} fusion(...)', '',
             t + 27 * MS, 2 * MS],
            ['%copy.393 = f32[16000,72,3]{2,1,0} copy(...)', '',
             t + 29 * MS, 1 * MS]]
    return dict(devices={'/device:TPU:0': ops},
                spans=[['bench.window', 0, 100 * MS]])


def kernel_ctx(events, force_evals):
    return dict(trace=devtrace.reduce(events),
                counters=dict(force_evals=force_evals))


@pytest.mark.parametrize('suffix', ['_half', ''])
def test_kernel_readers_find_each_kernel_by_name(suffix):
    mods = harness.metric_modules()
    ctx = kernel_ctx(named_trace(suffix), 3)
    got = {m: mods[m].read(ctx) for m in KERNEL_METRICS}
    assert got == {'snap_u_ms_per_eval': pytest.approx(2.0),
                   'snap_y_ms_per_eval': pytest.approx(20.0),
                   'snap_de_ms_per_eval': pytest.approx(5.0)}
    # the three kernels and the glue share of busy time add up to busy
    tr = ctx['trace']
    glue = mods['non_kernel_busy_pct'].read(ctx) / 100.0 * tr['busy_s']
    kernels = sum(got.values()) * 3 / 1000.0
    assert kernels + glue == pytest.approx(tr['busy_s'], rel=1e-9)


def test_kernel_seconds_reads_the_start_of_the_op_text_only():
    summary = dict(op_s={
        '%snap_y_half.1 = (f32[8,128]) custom-call()': 0.5,
        '%snap_y.2 = (f32[8,128]) custom-call()': 0.25,
        '%fusion.1 = f32[8] fusion(%snap_y_half.1)': 0.125})
    assert named.kernel_seconds(summary, 'snap_y') == pytest.approx(0.75)
    assert named.kernel_seconds(summary, 'snap_u') is None


@pytest.mark.parametrize('recorded', ['force_2j14_trace.json.gz',
                                      'md_2j8_trace.json.gz'])
def test_kernel_readers_find_nothing_in_unnamed_traces(recorded):
    """The recorded chip traces name the kernels %_lambda_.*,
    %closed_call.* and %force_fn.*: no reader mistakes them for named
    ones, and none reads 0."""
    events = devtrace.load_saved(BENCH / 'tests' / 'data' / recorded)
    ctx = kernel_ctx(events, 7)
    mods = harness.metric_modules()
    for name in KERNEL_METRICS:
        assert mods[name].read(ctx) is None, name


def span(name, start_ms, dur_ms, parent):
    return (name, start_ms * MS, dur_ms * MS, parent)


def loop_spans():
    """A set-up call of one chunk, then the window's call of two chunks:
    chunks of 100 and 110 ms waiting 95 and 96 ms on the device."""
    return [
        span('md.chunk', 1, 50, 'md.run'), span('md.wait', 2, 1, 'md.chunk'),
        span('md.run', 0, 60, None),
        span('md.seed', 101, 5, 'md.run'),
        span('md.dispatch', 110, 2, 'md.chunk'),
        span('md.wait', 112, 95, 'md.chunk'),
        span('md.log', 207, 1, 'md.chunk'),
        span('md.chunk', 110, 100, 'md.run'),
        span('md.wait', 215, 96, 'md.chunk'),
        span('md.chunk', 210, 110, 'md.run'),
        span('md.run', 100, 230, None)]


def test_host_ms_per_chunk_reads_the_last_run():
    assert named.host_ms_per_chunk(loop_spans()) == pytest.approx(9.5)
    assert named.host_ms_per_chunk([]) is None
    assert named.host_ms_per_chunk(
        [span('md.run', 0, 10, None), span('md.seed', 1, 5, 'md.run')]) \
        is None


def test_md_host_reader_reads_the_programs_ring(monkeypatch):
    from repro.runtime import trace
    mod = harness.metric_modules()['md_host_ms_per_chunk']
    monkeypatch.setattr(trace, 'snapshot', loop_spans)
    assert mod.read({}) == pytest.approx(9.5)
    monkeypatch.setattr(trace, 'snapshot', list)
    assert mod.read({}) is None


def test_md_host_reader_without_the_recorder(monkeypatch):
    """A program without ``repro.runtime.trace`` gives no reading."""
    import repro.runtime
    mod = harness.metric_modules()['md_host_ms_per_chunk']
    monkeypatch.setitem(sys.modules, 'repro.runtime.trace', None)
    monkeypatch.delattr(repro.runtime, 'trace', raising=False)
    assert mod.read({}) is None
