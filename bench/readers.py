"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

A reader gets ``ctx``: the reduced trace (``devtrace.reduce``), the
driver's counters for the traced window, the device peaks and the
device.  Counts per force evaluation come from ``counts.py``; the number
of force evaluations in the traced window from the counters.
"""

from __future__ import annotations

import counts
import devtrace


def stage(ctx, name):
    c = ctx['counters']
    s = counts.stages(int(c['twojmax']), int(c['atoms']),
                      int(c['npairs']))[name]
    n = float(c['force_evals']) / ctx['trace']['n_devices']
    return counts.StageCount(s.flops * n, s.bytes * n)


def kernel_roofline(ctx, patterns, name):
    """Percent of the roofline of the kernel matched by ``patterns``, or
    None when no device op matches (the kernel is not on the path)."""
    sec = devtrace.matching(ctx['trace'], patterns)
    if sec is None:
        return None
    pct, _ = counts.roofline_share(stage(ctx, name), sec, ctx['peaks'])
    return pct


def idle_pct(ctx):
    tr = ctx['trace']
    return 100.0 * (1.0 - tr['busy_s'] / tr['window_s'])
