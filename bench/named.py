"""Per-layer numbers read by the names the program gives its work.

Kernels: the program names each Pallas kernel (``pallas_call(name=...)``
in ``kernels/snap_*.py``), and the name becomes the kernel's HLO
instruction name, so the op's text in the profiler's ``XLA Ops`` line
starts with it: ``%snap_y_half.11 = ...`` (``%snap_y.3`` in the full
layout).  A prefix such as ``snap_y`` finds either layout.

Host phases: ``repro.runtime.trace`` keeps the program's host spans in
memory as ``(name, start_ns, dur_ns, parent)``; the MD device loop opens
``md.run`` around a ``run_nve`` call and ``md.chunk`` around each chunk,
with ``md.wait`` where the host blocks on the chunk's flags.

Where nothing carries the name (a program that names no kernel or records
no span), each function returns None, never 0.
"""

from __future__ import annotations


def kernel_seconds(summary: dict, prefix: str):
    """Exclusive device seconds of the ops whose text starts with
    ``%<prefix>``, or None when none does."""
    secs = [sec for name, sec in summary['op_s'].items()
            if name.startswith('%' + prefix)]
    return sum(secs) if secs else None


def kernel_ms_per_eval(ctx, prefix: str):
    """Milliseconds of the named kernel per force evaluation."""
    sec = kernel_seconds(ctx['trace'], prefix)
    if sec is None:
        return None
    return 1000.0 * sec / float(ctx['counters']['force_evals'])


def host_ms_per_chunk(spans):
    """Host milliseconds per chunk outside the wait on the device, in the
    last ``md.run`` span: the sum over its ``md.chunk`` spans of the chunk
    less its ``md.wait``, over the number of chunks."""
    runs = [s for s in spans if s[0] == 'md.run']
    if not runs:
        return None
    _, r0, r_dur, _ = max(runs, key=lambda s: s[1])
    inside = [s for s in spans if r0 <= s[1] and s[1] + s[2] <= r0 + r_dur]
    chunks = [s[2] for s in inside if s[0] == 'md.chunk']
    if not chunks:
        return None
    waits = [s[2] for s in inside if s[0] == 'md.wait']
    return (sum(chunks) - sum(waits)) / len(chunks) / 1e6
