"""Force-evaluation-as-a-service demo: SNAP behind a request queue.

Spins up a :class:`ForceServer` over a small bucket table, fires a
deterministic open-loop request stream at it (a seeded fraction carry
NaN coordinates or are too dense for the neighbor budget), and prints
the per-request outcomes plus the service health report.  Bad requests
come back as *typed errors with diagnostics* — the healthy requests
sharing their batch are unaffected and bitwise-identical to a solo
evaluation.

    PYTHONPATH=src python examples/serve_forces.py [--requests 12]
        [--impl jnp|kernel] [--fraction-bad 0.25]
"""
import argparse

import numpy as np

from repro.core.snap import SnapConfig
from repro.launch.request_queue import BucketTable, ServiceError
from repro.launch.serve_forces import ForceServer, run_open_loop
from benchmarks.b_serve import make_load, TABLE, TWOJMAX, RCUT


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--requests', type=int, default=12)
    ap.add_argument('--impl', choices=('jnp', 'kernel'), default='jnp')
    ap.add_argument('--fraction-bad', type=float, default=0.25)
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args()

    cfg = SnapConfig(twojmax=TWOJMAX, rcut=RCUT)
    beta = np.random.default_rng(args.seed).normal(size=cfg.ncoeff) * 5e-3
    schedule, plan = make_load(args.requests, beta,
                               fraction_bad=args.fraction_bad,
                               seed=args.seed)
    print(f'bucket table: {[b.key for b in TABLE.all_buckets()]}')
    print(f'poison plan: {plan or "(none)"}')

    srv = ForceServer(TABLE, impl=args.impl)
    health = run_open_loop(srv, schedule)

    print('\nper-request outcomes:')
    for i in range(args.requests):
        rid = f'r{i}'
        res = srv.result(rid)
        if isinstance(res, ServiceError):
            print(f'  {rid}: {type(res).__name__}: {res}')
        else:
            fmax = float(np.abs(res.forces).max())
            print(f'  {rid}: E={res.energy:+.6f} eV  |F|max={fmax:.4f} '
                  f'bucket={res.bucket_key} impl={res.impl} '
                  f'latency={res.latency * 1e3:.1f}ms')

    print('\nservice health:')
    for k, v in health.summary().items():
        print(f'  {k}: {v}')


if __name__ == '__main__':
    main()
