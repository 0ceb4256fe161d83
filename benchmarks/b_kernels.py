"""Sec. VI kernel benchmarks: Pallas (interpret-mode) vs pure-jnp stage
implementations at matched sizes, half-plane vs full-plane layouts, the
Y_TILE sweep, and the HLO-derived traffic comparison.

NOTE interpret mode runs the kernel body as Python/jnp per grid step — the
timing numbers here validate plumbing overheads and give the VMEM
working-set accounting; real speedups require TPU hardware.  The HLO
bytes/FLOP numbers are machine-independent (trip-count-corrected analysis
of the optimized HLO, see launch/hlo_cost.py) and are the tracked
perf-trajectory artifact for the half-plane layout:

- ``flops_dot``: one-hot matmul FLOPs — the Y kernel's MXU work.
- ``plane_bytes``: every consumption of a plane-shaped tensor
  ([idxu_max | idxu_half_max, lanes]); includes single-pass kernel
  interiors, so it is a conservative (noisy-low) reduction estimate.
- ``plane_bytes_loop``: plane traffic inside trip-counted grid loops —
  the Y kernel's per-COO-tile U-plane refetches, the traffic a TPU
  actually re-reads from HBM.  This is the headline ≥1.8x gate enforced
  in CI.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import (emit, snap_problem, snap_ulisttot, time_fn,
                     write_bench_json)

Y_TILE_SWEEP = (256, 512, 1024)


def _stage_rows(cfg, beta, dx, dy, dz, maskj, twojmax, natoms):
    """Per-stage timings, half vs full layout at matched inputs.

    The Pallas stages are timed *directly in plane layout* (exactly the
    tensors the pipeline passes between them) — not through the
    layout-converting test wrappers, whose mirror expansions / gathers
    would bias the half rows with work the pipeline never does.
    """
    from repro.core import bispectrum as bs
    from repro.kernels.ops import _kernel_layout, _self_planes
    from repro.kernels.snap_fused_de import snap_fused_de_pallas
    from repro.kernels.snap_fused_de_half import snap_fused_de_half_pallas
    from repro.kernels.snap_u import snap_u_half_pallas, snap_u_pallas
    from repro.kernels.snap_y import (snap_y_half_pallas, snap_y_pallas,
                                      y_coef)
    idx = cfg.index
    rows = {}

    ui_r = jax.jit(lambda: snap_ulisttot(cfg, dx, dy, dz, maskj))
    t_ur = time_fn(lambda: ui_r())
    ut = ui_r()
    y_jnp = jax.jit(lambda u: bs.compute_ylist(u, beta, idx))
    t_yr = time_fn(y_jnp, ut)
    rows['jnp'] = dict(snap_u_s=t_ur, snap_y_s=t_yr)

    disp, _, _ = _kernel_layout(cfg, dx, dy, dz, maskj, jnp.float32)
    geo = dict(twojmax=twojmax, rcut=cfg.rcut, rmin0=cfg.rmin0,
               rfac0=cfg.rfac0, switch_flag=cfg.switch_flag,
               interpret=True)
    stage_fns = dict(
        # the half walk takes beta itself, the full kernel a table
        half=(snap_u_half_pallas, snap_y_half_pallas, lambda b, _: b,
              snap_fused_de_half_pallas, 'half'),
        full=(snap_u_pallas, snap_y_pallas, y_coef,
              snap_fused_de_pallas, 'full'),
    )
    for layout, (u_fn, y_fn, coef_fn, de_fn, selfp) in stage_fns.items():
        u_jit = jax.jit(lambda d, f=u_fn: f(d, **geo))
        t_uk = time_fn(u_jit, disp)
        ut_r, ut_i = u_jit(disp)
        ut_r = ut_r + _self_planes(cfg, jnp.float32, selfp)
        coef = coef_fn(beta, twojmax).astype(jnp.float32)
        y_jit = jax.jit(lambda a, b, c, f=y_fn: f(
            a, b, c, twojmax=twojmax, interpret=True))
        t_yk = time_fn(y_jit, ut_r, ut_i, coef)
        y_r, y_i = y_jit(ut_r, ut_i, coef)
        de_jit = jax.jit(lambda d, a, b, f=de_fn: f(d, a, b, **geo))
        t_dek = time_fn(de_jit, disp, y_r, y_i)
        rows[layout] = dict(snap_u_s=t_uk, snap_y_s=t_yk, fused_de_s=t_dek)
        for stage, t in (('snap_u', t_uk), ('snap_y', t_yk),
                         ('fused_de', t_dek)):
            emit(f'kernel_{stage}_pallas_{layout}_2J{twojmax}_N{natoms}',
                 t, '')
    emit(f'kernel_snap_u_jnp_2J{twojmax}_N{natoms}', t_ur, '')
    emit(f'kernel_snap_y_jnp_2J{twojmax}_N{natoms}', t_yr, '')
    return rows, ut


def _y_tile_sweep(cfg, beta, ut, twojmax, tiles=Y_TILE_SWEEP):
    """Sweep the Y kernel's COO tile size (half layout); best wall-clock
    wins.  Returns {tile: seconds, ..., 'best_tile': int}."""
    from repro.kernels.ops import snap_yi_kernel
    out = {}
    for tile in tiles:
        fn = jax.jit(lambda u: snap_yi_kernel(
            cfg, u, beta, dtype=jnp.float32, interpret=True, y_tile=tile))
        out[str(tile)] = time_fn(fn, ut)
        emit(f'kernel_snap_y_tile{tile}_2J{twojmax}', out[str(tile)], '')
    best = min(tiles, key=lambda t: out[str(t)])
    out['best_tile'] = int(best)
    emit(f'kernel_snap_y_best_tile_2J{twojmax}', 0.0, str(best))
    return out


def hlo_traffic_comparison(cfg, beta, dx, dy, dz, nbr_idx, maskj):
    """Half vs full U->Y->dE pipeline: trip-count-corrected HLO cost.

    Returns per-layout {flops_dot, hbm_bytes, plane_bytes,
    plane_bytes_loop} plus the reduction ratios.  ``plane_bytes_loop``
    (grid-revisit plane traffic) is the number the half-plane layout is
    designed to halve; CI fails if it regresses below 1.8x.
    """
    from repro.kernels.common import LANES
    from repro.kernels.ops import snap_force_pipeline
    from repro.launch.hlo_cost import pipeline_plane_cost
    idx = cfg.index
    plane_rows = (idx.idxu_max, idx.idxu_half_max)
    # planes appear both as per-grid-step [rows, LANES] blocks and as
    # whole inter-stage [rows, natoms_pad] tensors — count both widths
    natoms_pad = -(-dx.shape[0] // LANES) * LANES
    lane_cols = tuple({LANES, natoms_pad})
    out = {}
    for layout in ('half', 'full'):
        def fn(a, b, c, nbr, m, _layout=layout):
            return snap_force_pipeline(
                cfg, beta, 0.0, a, b, c, nbr, m, dtype=jnp.float32,
                interpret=True, layout=_layout)
        cost = pipeline_plane_cost(fn, (dx, dy, dz, nbr_idx, maskj),
                                   plane_rows, lane_cols=lane_cols)
        out[layout] = {k: cost[k] for k in
                       ('flops_dot', 'hbm_bytes', 'plane_bytes',
                        'plane_bytes_loop')}
    out['reduction'] = {
        k: out['full'][k] / max(out['half'][k], 1.0)
        for k in out['full']}
    for k, v in out['reduction'].items():
        emit(f'kernel_pipeline_half_vs_full_{k}_x', 0.0, f'{v:.2f}')
    return out


def run(quick=True, out_dir=None):
    natoms = 128
    twojmax = 8
    cfg, beta, disp, nbr_idx, mask = snap_problem(natoms, twojmax)
    beta = jnp.asarray(beta)
    idx = cfg.index

    dx, dy, dz = (jnp.asarray(disp[..., i]) for i in range(3))
    maskj = jnp.asarray(mask)
    nbrj = jnp.asarray(nbr_idx)

    stages, ut = _stage_rows(cfg, beta, dx, dy, dz, maskj, twojmax, natoms)
    tile_sweep = {f'2J{twojmax}': _y_tile_sweep(cfg, beta, ut, twojmax)}
    if not quick:
        # the 2J=14 sweep needs coarser tiles: ~1.06M half-COO entries
        cfg14, beta14, disp14, _, mask14 = snap_problem(128, 14)
        ut14 = snap_ulisttot(
            cfg14, jnp.asarray(disp14[..., 0]), jnp.asarray(disp14[..., 1]),
            jnp.asarray(disp14[..., 2]), jnp.asarray(mask14))
        tile_sweep['2J14'] = _y_tile_sweep(
            cfg14, jnp.asarray(beta14), ut14, 14,
            tiles=(4096, 8192, 16384))

    traffic = hlo_traffic_comparison(cfg, beta, dx, dy, dz, nbrj, maskj)

    write_bench_json('kernel_stages', dict(
        twojmax=twojmax, natoms=natoms, interpret=True,
        stages=stages,
        y_tile_sweep=tile_sweep,
        hlo_traffic=traffic,
        # legacy keys kept for cross-PR trajectory diffs
        snap_u=dict(pallas_s=stages['half']['snap_u_s'],
                    jnp_s=stages['jnp']['snap_u_s']),
        snap_y=dict(pallas_s=stages['half']['snap_y_s'],
                    jnp_s=stages['jnp']['snap_y_s']),
        fused_de=dict(pallas_s=stages['half']['fused_de_s']),
    ), out_dir, interpret=True)

    # VMEM working-set accounting (the paper's occupancy argument, Sec VI)
    for name, iu in (('full', idx.idxu_max), ('half', idx.idxu_half_max)):
        vmem = (26 * 4 * 128 * 4          # disp block
                + 2 * iu * 128 * 4        # ulisttot out planes
                + 4 * (twojmax + 1) ** 2 * 128 * 4)   # live recursion
        emit(f'kernel_snap_u_vmem_per_block_{name}_2J{twojmax}', 0.0,
             f'{vmem / 1e6:.2f}MB_of_128MB')
    return True


if __name__ == '__main__':
    run()
