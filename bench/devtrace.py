"""Profiler trace capture and its reduction to device busy time, op times
and idle gaps.

``capture`` runs a callable under ``jax.profiler.trace``; ``load`` reads
the ``.xplane.pb`` it wrote into plain lists, and ``reduce`` (pure
Python, tested on a recorded chip trace) turns those lists into the
numbers the per-layer metrics read:

- device ops: the events of each TPU plane's ``XLA Ops`` line, clipped
  to the traced window (the host span ``bench.window``), each timed by
  its own (exclusive) time: a ``while`` or call op that encloses others
  keeps only the time none of them covers;
- busy: the union of those intervals per chip, averaged over the chips;
- idle gaps: the holes in that union, each put down to the innermost
  benchmark host span (``bench.*``) that covers its midpoint.
"""

from __future__ import annotations

from collections import defaultdict
from pathlib import Path

OPS_LINE = 'XLA Ops'
DEVICE_PREFIX = '/device:TPU:'
SPAN_PREFIX = 'bench.'
WINDOW_SPAN = 'bench.window'


def capture(log_dir: Path, fn):
    import jax
    log_dir.mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(str(log_dir)):
        out = fn()
    return out


def _stat(event, names):
    try:
        for k, v in event.stats:
            if k in names:
                return str(v)
    except Exception:   # stats of some events cannot be decoded
        return ''
    return ''


def load(log_dir: Path, chips: int) -> dict:
    """{'devices': {plane: [[name, long_name, start_ns, dur_ns], ...]},
    'spans': [[name, start_ns, dur_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData
    paths = sorted(Path(log_dir).glob('plugins/profile/*/*.xplane.pb'),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise RuntimeError(f'no profiler trace under {log_dir}')
    data = ProfileData.from_file(str(paths[-1]))
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            if int(plane.name[len(DEVICE_PREFIX):]) >= chips:
                continue
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    ops.append([ev.name, _stat(ev, ('long_name',))[:300],
                                int(ev.start_ns), int(ev.duration_ns)])
            devices[plane.name] = ops
        elif not plane.name.startswith('/device:'):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append([ev.name, int(ev.start_ns),
                                      int(ev.duration_ns)])
    if not devices:
        raise RuntimeError('the trace holds no TPU device plane')
    return dict(devices=devices, spans=spans)


def save(events: dict, path: Path):
    """The loaded events, gzipped JSON (what the tests replay)."""
    import gzip
    import json
    with gzip.open(path, 'wt') as f:
        json.dump(events, f)


def load_saved(path: Path) -> dict:
    import gzip
    import json
    with gzip.open(path, 'rt') as f:
        return json.load(f)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(clipped, out, scale):
    """Add each op's exclusive time (its span less its nested ops') into
    ``out``; ``clipped`` is [(name, start, end)] of one device."""
    stack = []          # [name, start, end, ns covered by nested ops]

    def pop():
        name, s, e, inner = stack.pop()
        out[name] += (e - s - inner) * scale

    for name, s, e in sorted(clipped, key=lambda o: (o[1], -o[2])):
        while stack and s >= stack[-1][2]:
            pop()
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([name, s, e, 0.0])
    while stack:
        pop()


def short_name(text: str) -> str:
    """An HLO instruction's text cut to its name, result type and kind
    (and custom-call target), without layouts or operands."""
    import re
    t = re.sub(r'\{[^{}]*\}', '', text)
    m = re.match(r'(\S+ = .*?) ([\w-]+)\(', t)
    head = f'{m.group(1)} {m.group(2)}' if m else t[:120]
    target = re.search(r'custom_call_target="([^"]+)"', t)
    return head + (f' {target.group(1)}' if target else '')


def reduce(events: dict, top: int = 10) -> dict:
    """Busy and window seconds, exclusive op seconds by name (mean over
    chips), and idle seconds by host span, over the traced window."""
    windows = [s for s in events['spans'] if s[0] == WINDOW_SPAN]
    if not windows:
        raise RuntimeError('the trace holds no bench.window span')
    w0 = windows[-1][1]
    w1 = w0 + windows[-1][2]
    spans = [s for s in events['spans']
             if s[0] != WINDOW_SPAN and s[1] < w1 and s[1] + s[2] > w0]
    n_dev = len(events['devices'])
    op_ns = defaultdict(float)
    long_names = {}
    busy_ns = 0.0
    idle = defaultdict(float)
    for ops in events['devices'].values():
        clipped = []
        for name, long_name, start, dur in ops:
            s, e = max(start, w0), min(start + dur, w1)
            if e <= s:
                continue
            clipped.append((name, s, e))
            long_names.setdefault(name, long_name)
        _self_times(clipped, op_ns, 1.0 / n_dev)
        busy = _union([(s, e) for _, s, e in clipped])
        busy_ns += sum(e - s for s, e in busy) / n_dev
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            mid = 0.5 * (gs + ge)
            cover = [s for s in spans if s[1] <= mid <= s[1] + s[2]]
            label = (min(cover, key=lambda s: s[2])[0][len(SPAN_PREFIX):]
                     if cover else 'outside_spans')
            idle[label] += (ge - gs) / n_dev
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])
    short = defaultdict(float)
    for k, v in ops_sorted:
        short[short_name(k)] += v
    return dict(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_ns * 1e-9,
        op_s={k: v * 1e-9 for k, v in ops_sorted},
        long_names=long_names,
        device_ops=[[k, v * 1e-9] for k, v in
                    sorted(short.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v * 1e-9] for k, v in
                   sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
        n_devices=n_dev)


def matching(summary: dict, patterns):
    """Exclusive seconds of the device ops whose name or long name
    matches any of ``patterns`` (regular expressions), or None when none
    matches: a kernel that is not on the path is not read as 0."""
    import re
    total, hit = 0.0, False
    for name, sec in summary['op_s'].items():
        text = name + ' ' + summary['long_names'].get(name, '')
        if any(re.search(p, text) for p in patterns):
            total += sec
            hit = True
    return total if hit else None
