"""The reduction from profiler events to busy time, op times and idle gaps:
exact on a hand-made trace, and on a small trace recorded on the chip
(``data/force_2j14_trace.json.gz``, the force cell's traced window, and
``data/md_2j8_trace.json.gz``, the MD cell's cut to its first 0.25 s)."""

import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402
import harness  # noqa: E402

MS = 1_000_000


def hand_trace():
    # window 0..100 ms; device busy 10-30 (two overlapping ops) and 60-70
    return dict(
        devices={'/device:TPU:0': [
            ['fusion.1', '', 10 * MS, 15 * MS],
            ['my_kernel', 'custom-call my_kernel', 20 * MS, 10 * MS],
            ['my_kernel', 'custom-call my_kernel', 60 * MS, 10 * MS],
            ['late', '', 95 * MS, 20 * MS]]},
        spans=[['bench.window', 0, 100 * MS],
               ['bench.force_call', 0, 50 * MS],
               ['bench.readback', 50 * MS, 40 * MS]])


def test_reduce_hand_trace():
    s = devtrace.reduce(hand_trace())
    assert s['window_s'] == pytest.approx(0.1)
    # 10-30, 60-70 and 95-100 (clipped to the window)
    assert s['busy_s'] == pytest.approx(0.035)
    assert s['op_s']['my_kernel'] == pytest.approx(0.02)
    assert s['op_s']['late'] == pytest.approx(0.005)
    # each gap goes to the innermost span over its midpoint: 0-10 and
    # 30-60 to force_call, 70-95 to readback
    assert s['idle_gaps'] == [['force_call', pytest.approx(0.040)],
                              ['readback', pytest.approx(0.025)]]
    events = hand_trace()
    events['spans'] = events['spans'][:2]
    gaps = dict(devtrace.reduce(events)['idle_gaps'])
    assert gaps['outside_spans'] == pytest.approx(0.025)
    assert devtrace.matching(s, [r'my_kernel']) == pytest.approx(0.02)
    assert devtrace.matching(s, [r'no_such_kernel']) is None


def test_nested_ops_count_their_own_time():
    # a while op over 0-50 encloses a kernel over 10-30: the loop keeps 30
    events = dict(devices={'/device:TPU:0': [
        ['%while.1 = while', '', 0, 50 * MS],
        ['%k = custom-call', '', 10 * MS, 20 * MS]]},
        spans=[['bench.window', 0, 100 * MS]])
    s = devtrace.reduce(events)
    assert s['busy_s'] == pytest.approx(0.05)
    assert s['op_s']['%while.1 = while'] == pytest.approx(0.03)
    assert s['op_s']['%k = custom-call'] == pytest.approx(0.02)


def test_short_names_drop_layouts_and_operands():
    text = ('%_lambda_.3 = (f32[652,2048]{1,0:T(8,128)S(1)}, f32[652,2048]'
            '{1,0:T(8,128)S(1)}) custom-call(f32[26,4,2048]{2,1,0:T(4,128)S'
            '(1)} %dynamic-update-slice.1), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={f32[26,4,2048]'
            '{2,1,0}}')
    assert devtrace.short_name(text) == (
        '%_lambda_.3 = (f32[652,2048], f32[652,2048]) custom-call '
        'tpu_custom_call')
    assert devtrace.short_name('%fusion.2 = f32[2000,3]{1,0} fusion(f32'
                               '[2000,3] %p), kind=kLoop') == \
        '%fusion.2 = f32[2000,3] fusion'


def test_reduce_recorded_chip_trace():
    events = devtrace.load_saved(BENCH / 'tests' / 'data' /
                                 'force_2j14_trace.json.gz')
    s = devtrace.reduce(events)
    assert 0 < s['busy_s'] <= s['window_s']
    assert s['n_devices'] == 1
    assert len(s['device_ops']) <= 10 and len(s['idle_gaps']) <= 10
    total_op = sum(s['op_s'].values())
    assert total_op == pytest.approx(s['busy_s'], rel=0.05)
    # each kernel pattern of the metric files finds its own kernel here,
    # and only it: U, Y and dE are the instructions %_lambda_.3, .4, .5
    mods = harness.metric_modules()
    for name, op in (('snap_u_roofline', '%_lambda_.3 '),
                     ('snap_y_half_roofline', '%_lambda_.4 '),
                     ('fused_de_half_roofline', '%_lambda_.5 ')):
        hits = [n for n in s['op_s'] if any(
            re.search(p, n) for p in mods[name].PATTERNS)]
        assert [n[:len(op)] for n in hits] == [op], name
    # Y is nearly all of the 2J=14 call (7 calls of 0.609 s)
    assert devtrace.matching(
        s, mods['snap_y_half_roofline'].PATTERNS) > 0.95 * s['busy_s']
    # and the readers give shares below 100%
    ctx = dict(trace=s, counters=events['counters'],
               peaks=harness.peaks_for('TPU v5 lite'))
    for name in ('snap_u_roofline', 'snap_y_half_roofline',
                 'fused_de_half_roofline', 'force_mfu_pct',
                 'non_kernel_busy_pct', 'device_idle_pct.steps'):
        value = mods[name].read(ctx)
        assert 0 < value < 100, (name, value)


def test_reduce_recorded_md_trace():
    """The device loop's ops sit inside a while op; each is timed once."""
    events = devtrace.load_saved(BENCH / 'tests' / 'data' /
                                 'md_2j8_trace.json.gz')
    s = devtrace.reduce(events)
    assert sum(s['op_s'].values()) == pytest.approx(s['busy_s'], rel=1e-6)
    assert not s['device_ops'][0][0].split(' = ')[0].startswith('%while')
    mods = harness.metric_modules()
    for name in ('snap_u_roofline', 'snap_y_half_roofline',
                 'fused_de_half_roofline'):
        assert devtrace.matching(s, mods[name].PATTERNS) > 0, name
