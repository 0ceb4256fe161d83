"""Benchmark of the SNAP force engine on TPU: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  One process holds the chip: it loads
the cell's files (``bench/workloads/<cell>.json`` and the configuration
and traffic mix it names), builds the inputs from ``--seed``, warms up
every shape the window uses, measures for ``--seconds``, compares what
the timed path produced with the plain float64 reference
(``bench/reference.py``), and prints one JSON line last on stdout.  With
``--trace 1`` the window is a shorter traced one and the line carries
the per-layer metrics (``bench/metrics/*.py``) instead of the end-to-end
ones.  It exits nonzero, with no result, when JAX finds no TPU or fewer
chips than the cell asks for.

JAX's persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when
that is set, otherwise ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import argparse
import gc
import os
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / 'src'))

import harness  # noqa: E402
import devtrace  # noqa: E402

TRACE_DIR = BENCH.parent / '.bench_runs'


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_cache():
    import jax
    path = os.environ.get('JAX_COMPILATION_CACHE_DIR') or str(
        BENCH.parent / '.jax_cache')
    jax.config.update('jax_compilation_cache_dir', path)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    return path


def per_layer(run, summary, device, peaks, window, bench=BENCH):
    """Every metric reader under ``metrics/`` whose ``WORKLOADS`` lists
    this cell; a reader that finds nothing returns None and is left out."""
    ctx = dict(trace=summary, counters=run.counters, peaks=peaks,
               device=device, snap=run.snap, window_s=window,
               workload=run.name)
    out = {}
    for name, mod in harness.metric_modules(bench).items():
        if run.name not in mod.WORKLOADS:
            continue
        value = mod.read(ctx)
        if value is not None:
            out[name] = dict(value=value, unit=mod.UNIT)
    return out


def main(argv=None, require_tpu=True, files=None, driver=None):
    t_start = harness.process_start_time()
    args = parse(argv)
    files = files or harness.cell_files(args.workload)
    driver = driver or harness.driver_for(files['traffic'])
    enable_cache()
    import jax
    jax.config.update('jax_enable_x64', True)   # the MD carry is float64
    chips = int(files['workload']['chips'])
    device = harness.device_info(chips, require_tpu)
    compiles = harness.compile_counter()
    run = harness.Run(name=args.workload, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace), **files)
    run.counters['setup_init_s'] = time.time() - t_start

    state = driver.setup(run)
    n_setup_compiles = compiles['n']
    setup_s = time.time() - t_start

    summary = None
    window = args.seconds
    if run.trace:
        window = min(args.seconds, float(files['traffic']['trace_seconds']))
        log_dir = TRACE_DIR / f'{args.workload}.{args.seed}'
        devtrace.capture(log_dir, lambda: driver.measure(state, run, window))
        events = devtrace.load(log_dir, chips)
        shutil.rmtree(log_dir / 'plugins')
        devtrace.save(events, log_dir / 'events.json.gz')
        summary = devtrace.reduce(events)
    else:
        driver.measure(state, run, window)
    run.counters['window_compiles'] = compiles['n'] - n_setup_compiles
    device['memory_peak_bytes'] = harness.memory_peak_bytes(chips)

    driver.release(state)
    gc.collect()
    compared, attempted, failed = driver.check(state, run)
    correct = all(v <= lim for _, v, lim in compared) and failed == 0

    if run.trace:
        peaks = harness.peaks_for(device['kind']) if require_tpu else None
        metrics = per_layer(run, summary, device, peaks, window)
        device['busy_s'] = summary['busy_s']
        device['window_s'] = summary['window_s']
        breakdown = dict(device_ops=summary['device_ops'],
                         idle_gaps=summary['idle_gaps'])
    else:
        metrics = {k: dict(value=v, unit=u)
                   for k, (v, u) in driver.end_to_end(state, run).items()}
        metrics['setup_s'] = dict(value=setup_s, unit='s')
        breakdown = None
    info = ' '.join(f'{k}={v}' for k, v in sorted(run.counters.items()))
    print(f'bench: {args.workload} seed={args.seed} {info}',
          file=sys.stderr)
    for name, value, limit in compared:
        print(f'compared {name} = {value!r} limit {limit!r}',
              file=sys.stderr)
    print(harness.result_line(correct, attempted, failed, metrics, device,
                              compared, breakdown), flush=True)
    return correct


if __name__ == '__main__':
    main()
