"""Bring-up smoke test: the SNAP force engine's main paths on a TPU.

    python chip_smoke.py               # one chip: device, force, md, serve
    python chip_smoke.py --four-chips  # atom-sharded device-loop MD, 4 chips

Everything runs in this one process, which holds the chip.  Inputs are
built from seeds: the paper's bcc boxes (``md.lattice.paper_box`` +
``perturb``) and a random linear SNAP model.  Each phase prints one line
of its own numbers and raises on a failed check, so the script exits
nonzero and prints no result.  Only when every phase passed is the last
line of stdout the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.

Times printed here are one-off readings of a single cold process, not
benchmark results.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / 'src'))

F32_BAR = 1e-5          # DESIGN.md precision policy: f32 vs the f64 oracle
BF16_BAR = 1e-2         # mxu_dtype=bfloat16 feed
TRAJ_POS_BAR = 1e-6     # Å, 4-shard vs 1-shard positions (summation order)
REF_BLOCK_ATOMS = 50    # atoms per block of the f64 oracle (host memory)
# random displacement of the lattice sites, Å: the largest that keeps all
# 26 neighbours of every atom of the 2000-atom box inside rcut (at 0.04 Å
# two atoms lose one).  The colder the lattice, the further the 26 pair
# terms of each force cancel, and the nearer f32 round-off alone (the jnp
# adjoint in f32, no kernels) comes to the 1e-5 bar: about 8e-6 at 0.02 Å.
PERTURB_A = 0.03


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def rel_err(x, ref):
    import numpy as np
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def phase_device(count):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != 'tpu':
        sys.exit(f'chip_smoke: needs a TPU, but JAX found platform '
                 f'{platform!r}')
    check(len(devs) >= count, f'need {count} chips, found {len(devs)}')
    dev = dict(platform=platform, kind=devs[0].device_kind, count=len(devs))
    print(f'device: platform={platform} kind={dev["kind"]} '
          f'count={dev["count"]}', flush=True)
    return dev


def paper_problem(config, natoms=None, seed=0):
    """``configs/<config>.py`` at its published widths: the perturbed bcc
    box, its padded neighbour lists and a seeded beta."""
    import numpy as np

    from repro.md.lattice import paper_box, perturb
    from repro.md.neighbor import brute_neighbors

    spec = importlib.import_module(f'repro.configs.{config}').CONFIG
    cfg = spec['snap']
    pos, box = paper_box(natoms or spec['natoms'], spec['lattice_a'])
    pos = perturb(pos, PERTURB_A, seed=seed + 1)
    nbr_idx, mask, disp, _ = brute_neighbors(pos, box, cfg.rcut,
                                             spec['nnbor'])
    beta = np.random.default_rng(seed).normal(size=cfg.ncoeff) * 5e-3
    return spec, cfg, pos, box, nbr_idx, mask, disp, beta


def oracle_forces(cfg, beta, disp, nbr_idx, mask):
    """The jnp adjoint in f64 on the host CPU backend, in atom blocks."""
    import jax

    from repro.core.snap import energy_forces

    natoms = disp.shape[0]
    chunks = (natoms // REF_BLOCK_ATOMS
              if natoms % REF_BLOCK_ATOMS == 0 else 1)
    cpu = jax.devices('cpu')[0]
    args = jax.device_put((beta, disp[..., 0], disp[..., 1], disp[..., 2],
                           nbr_idx, mask), cpu)
    fn = jax.jit(lambda b, dx, dy, dz, ni, m: energy_forces(
        cfg, b, 0.0, dx, dy, dz, ni, m, impl='adjoint', atom_chunks=chunks))
    e, _, f = jax.block_until_ready(fn(*args))
    return float(e), f


def phase_force(config, mxu_bf16=False):
    """One compiled kernel force call against the f64 oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.snap import energy_forces

    spec, cfg, _, _, nbr_idx, mask, disp, beta = paper_problem(config)
    check(int(mask.sum(1).min()) == int(mask.sum(1).max()) == spec['nnbor'],
          f'{config}: expected {spec["nnbor"]} neighbours per atom')
    e_ref, f_ref = oracle_forces(cfg, beta, disp, nbr_idx, mask)

    f32 = np.float32
    args = (jnp.asarray(beta, f32), jnp.asarray(disp[..., 0], f32),
            jnp.asarray(disp[..., 1], f32), jnp.asarray(disp[..., 2], f32),
            jnp.asarray(nbr_idx), jnp.asarray(mask))
    kw = dict(mxu_dtype=jnp.bfloat16) if mxu_bf16 else {}
    fn = jax.jit(lambda b, dx, dy, dz, ni, m: energy_forces(
        cfg, b, 0.0, dx, dy, dz, ni, m, impl='kernel', interpret=False,
        **kw))
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    t_compile = time.perf_counter() - t0
    kernels = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    check(kernels == 3, f'{config}: {kernels} Mosaic kernels in the '
                        'compiled program, expected U, Y and dE')
    t0 = time.perf_counter()
    jax.block_until_ready(compiled(*args))
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    e, _, f = jax.block_until_ready(compiled(*args))
    t_warm = time.perf_counter() - t0

    bar = BF16_BAR if mxu_bf16 else F32_BAR
    err_f = rel_err(f, f_ref)
    err_e = abs(float(e) - e_ref) / abs(e_ref)
    feed = 'bf16' if mxu_bf16 else 'f32'
    print(f'force {spec["name"]} feed={feed} natoms={disp.shape[0]} '
          f'nnbor={spec["nnbor"]} kernels={kernels} '
          f'rel_force_err={err_f!r} rel_energy_err={err_e!r} bar={bar} '
          f'compile_s={t_compile:.3f} first_call_s={t_first:.6f} '
          f'warm_call_s={t_warm:.6f} (one-off reading, not a benchmark)',
          flush=True)
    check(np.isfinite(np.asarray(f)).all(), f'{config}: non-finite forces')
    check(err_f <= bar, f'{config} {feed}: force error {err_f} > {bar}')
    check(err_e <= bar, f'{config} {feed}: energy error {err_e} > {bar}')


def nve_run(config, natoms, n_steps, shards=1, log_every=10):
    """``run_nve(loop='device', impl='kernel')`` from a seeded lattice."""
    import numpy as np

    from repro.md.integrate import MDState, init_velocities, run_nve

    spec, cfg, pos, box, *_, beta = paper_problem(config, natoms)
    state = MDState(pos=pos, vel=init_velocities(len(pos), 300.0), box=box)
    cache = {}
    t0 = time.perf_counter()
    state, thermo = run_nve(cfg, beta, 0.0, state, n_steps, impl='kernel',
                            loop='device', log_every=log_every,
                            shards=shards, fn_cache=cache)
    wall = time.perf_counter() - t0
    etot = np.array([t['etot'] for t in thermo])
    check(np.isfinite(etot).all(), f'non-finite energies: {etot}')
    check(np.isfinite(state.pos).all() and np.isfinite(state.vel).all(),
          'non-finite MD state')
    return spec, state, thermo, cache, wall


def phase_md(natoms=2000, n_steps=20):
    from repro.analysis.retrace import trace_count

    spec, state, thermo, cache, wall = nve_run('snap_2j8', natoms, n_steps)
    e0, e1 = thermo[0]['etot'], thermo[-1]['etot']
    drift = abs(e1 - e0) / abs(e0)
    traces = trace_count(cache['device_trace_count'])
    print(f'md {spec["name"]} natoms={natoms} steps={n_steps} '
          f'log_rows={len(thermo)} etot0={e0!r} etot1={e1!r} '
          f'rel_nve_drift={drift!r} rebuilds={cache["device_rebuilds"]} '
          f'chunk_traces={traces} wall_s={wall:.3f} '
          f'(compile included; one-off reading)', flush=True)


def serve_requests(n_requests, twojmax, rcut, seed=0):
    """Mixed 16/54-atom requests of one model class."""
    import numpy as np

    from repro.core.snap import SnapConfig
    from repro.launch.request_queue import ForceRequest
    from repro.md.lattice import paper_box, perturb

    beta = np.random.default_rng(seed).normal(
        size=SnapConfig(twojmax=twojmax, rcut=rcut).ncoeff) * 5e-3
    reqs = []
    for i in range(n_requests):
        pos, box = paper_box(natoms=(16, 54)[i % 2])
        reqs.append(ForceRequest(
            f'r{i}', pos=perturb(pos, 0.03, seed=seed + i),
            box=np.asarray(box, float), beta=beta, twojmax=twojmax,
            rcut=rcut))
    return reqs


def serve_all(table, reqs, impl):
    from repro.launch.serve_forces import ForceServer
    srv = ForceServer(table, impl=impl, queue_depth=len(reqs))
    for r in reqs:
        srv.submit(r, now=0.0)
    now = 0.0
    while srv.queue.depth:
        _, dt = srv.step(now)
        now += max(dt, 1e-9)
    return srv, {r.req_id: srv.result(r.req_id) for r in reqs}


def phase_serve(n_requests=8, twojmax=8, rcut=3.0):
    """ForceServer(impl='kernel') on the chip vs impl='jnp' on the host."""
    import jax

    from repro.launch.request_queue import BucketTable
    from repro.launch.serve_forces import ForceResult

    table = BucketTable(model_classes=((twojmax, rcut),), n_pads=(16, 64),
                        nbor_ladder=(12,), batch=4)
    reqs = serve_requests(n_requests, twojmax, rcut)
    t0 = time.perf_counter()
    srv, got = serve_all(table, reqs, 'kernel')
    wall = time.perf_counter() - t0
    with jax.default_device(jax.devices('cpu')[0]):
        _, ref = serve_all(table, reqs, 'jnp')
    errs = []
    for rid, res in got.items():
        check(isinstance(res, ForceResult) and res.impl == 'kernel',
              f'request {rid} not served by the kernel path: {res!r}')
        check(isinstance(ref[rid], ForceResult), f'reference {rid}: '
                                                 f'{ref[rid]!r}')
        errs.append(max(rel_err(res.forces, ref[rid].forces),
                        abs(res.energy - ref[rid].energy)
                        / abs(ref[rid].energy)))
    h = srv.health()
    print(f'serve 2J={twojmax} rcut={rcut} requests={len(reqs)} '
          f'served={h.served} failed={h.failed} '
          f'degraded_steps={h.degraded_steps} '
          f'quarantined={list(h.quarantined)} '
          f'compile_counts={h.compile_counts} max_rel_err={max(errs)!r} '
          f'wall_s={wall:.3f} (compile included; one-off reading)',
          flush=True)
    check(h.served == len(reqs) and h.failed == 0, 'not every request '
                                                   'was served')
    check(h.degraded_steps == 0, f'{h.degraded_steps} degraded steps')
    check(not h.quarantined, f'quarantined buckets: {h.quarantined}')
    check(max(errs) <= F32_BAR, f'serve error {max(errs)} > {F32_BAR}')


def phase_four_chips(natoms=2000, n_steps=20):
    """Atom-sharded device-loop MD over 4 chips against 1 shard."""
    import numpy as np

    from repro.launch.sharding import make_atom_mesh

    mesh = make_atom_mesh(4)
    n_dev = len({d.id for d in mesh.devices.flat})
    check(n_dev == 4, f'atom mesh spans {n_dev} distinct devices, not 4')
    spec, s4, th4, _, wall4 = nve_run('snap_2j8', natoms, n_steps, shards=4)
    _, s1, th1, _, wall1 = nve_run('snap_2j8', natoms, n_steps, shards=1)
    d_pos = float(np.abs(s4.pos - s1.pos).max())
    d_pe = max(abs(a['pe'] - b['pe']) / abs(b['pe'])
               for a, b in zip(th4, th1))
    print(f'four_chips {spec["name"]} natoms={natoms} steps={n_steps} '
          f'mesh_devices={n_dev} max_abs_dpos_A={d_pos!r} '
          f'max_rel_dpe={d_pe!r} wall_s_4={wall4:.3f} '
          f'wall_s_1={wall1:.3f} (compile included; one-off reading)',
          flush=True)
    check(len(th4) == len(th1), 'thermo rows differ')
    check(d_pos <= TRAJ_POS_BAR, f'positions differ by {d_pos} Å')
    check(d_pe <= F32_BAR, f'potential energies differ by {d_pe}')


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n')[0])
    ap.add_argument('--four-chips', action='store_true',
                    help='run only the 4-shard device-loop MD against '
                         'shards=1 (needs 4 chips)')
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    jax.config.update('jax_enable_x64', True)   # the f64 oracle and MD carry

    dev = phase_device(4 if args.four_chips else 1)
    print(f'compile_cache: {cache_dir}', flush=True)
    if args.four_chips:
        phase_four_chips()
    else:
        phase_force('snap_2j14')
        phase_force('snap_2j8')
        phase_force('snap_2j8', mxu_bf16=True)
        phase_md()
        phase_serve()
    print(json.dumps(dict(ok=True, device=dev)))


if __name__ == '__main__':
    main()
