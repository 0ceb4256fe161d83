"""Readings that set a cell's limits: the program and its control.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 2 \\
        --variants program,bf16

For each variant and seed, in one process: the cell's set-up, a short
window at the cell's own load, and the comparison with the float64
reference, printed as one JSON line of the numbers ``correct`` compares.

- ``program``: the program as the configuration states it (float32, the
  Y kernel's matmuls at HIGHEST): the lower readings.
- ``bf16``: the program's own lower-precision path, the Y kernel fed in
  bfloat16 (``mxu_dtype=jnp.bfloat16``): the control, whose smallest
  reading is the upper end of each limit.  (HIGH, three bf16 passes, is
  not a control here: Mosaic refuses a dot at that precision.)

The benchmark's own runs never run this.  A TPU is required.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / 'src'))

import harness  # noqa: E402
import run as bench_run  # noqa: E402


def variant_kwargs(name: str) -> dict:
    import jax.numpy as jnp
    if name == 'program':
        return {}
    if name == 'bf16':
        return dict(mxu_dtype=jnp.bfloat16)
    raise ValueError(f'unknown variant {name!r}')


def readings(files, driver, seed, seconds, kwargs, name):
    run = harness.Run(name=name, seed=seed, seconds=seconds, trace=False,
                      **files)
    st = driver.setup(run, force_kwargs=kwargs)
    driver.measure(st, run, seconds)
    driver.release(st)
    compared, attempted, failed = driver.check(st, run)
    return dict(compared={n: v for n, v, _ in compared},
                limits={n: lim for n, _, lim in compared},
                attempted=attempted, failed=failed)


def main(argv=None, require_tpu=True):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--seconds', type=float, required=True)
    ap.add_argument('--variants', default='program,bf16')
    args = ap.parse_args(argv)
    files = harness.cell_files(args.workload)
    driver = harness.driver_for(files['traffic'])
    bench_run.enable_cache()
    import jax
    jax.config.update('jax_enable_x64', True)
    device = harness.device_info(int(files['workload']['chips']),
                                 require_tpu)
    for variant in args.variants.split(','):
        kwargs = variant_kwargs(variant)
        for seed in (int(s) for s in args.seeds.split(',')):
            t0 = time.perf_counter()
            try:
                out = readings(files, driver, seed, args.seconds, kwargs,
                               args.workload)
            except Exception as exc:   # a control that crashes has failed
                out = dict(error=f'{type(exc).__name__}: {exc}'[:2000],
                           trace=traceback.format_exc()[-1500:])
            print(json.dumps(dict(workload=args.workload, variant=variant,
                                  seed=seed, device=device['kind'],
                                  seconds=time.perf_counter() - t0, **out)),
                  flush=True)


if __name__ == '__main__':
    main()
