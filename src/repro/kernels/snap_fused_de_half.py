"""Half-plane fused dE kernel (beyond-paper SNAP iteration).

Observation: the force contraction dE = 2 sum w Re(conj(dU) Y) has w == 0
for all rows 2*mb > j — yet the v1 kernel (like the reference) materializes
the FULL (j+1)^2 layer of u and of all three tangents at every level, only
to discard the mirrored half in the contraction.

This kernel carries ONLY the left rows (mb <= j/2) of u and du through
the recursion (shared helpers in :mod:`repro.kernels.common`: the
recursion needs prev rows mb <= j/2 of layer j-1; for even j the single
extra row is mirror-reconstructed on the fly), and it consumes the
adjoint Y **natively in half-plane layout** — ``[idxu_half_max, L]``
planes straight from :func:`repro.kernels.snap_y.snap_y_half_pallas`,
no full-plane reconstruction anywhere.  Each half layer j is contiguous
at ``idxu_half_block[j]`` so the per-level Y block is one static slice.

Counted effects vs v1 (per neighbor, 2J=8):
  - level-state elements stored:     285 -> 165   (1.73x fewer)
  - mirror transform ops:            ~480 -> ~60  (8x fewer)
  - VMEM live planes (u + 3 du):     2*(J+1)^2*4 -> ~half
  - Y planes streamed from HBM:      285 -> 155 rows (1.84x less traffic)

``snap_de_species_pallas`` is the same kernel on the species path's
five-channel per-pair array (x, y, z, w_j, rcut_ij): the pair's own
cutoff in the geometry, and ``w_j`` scaling the switching value and its
derivative (LAMMPS ``compute_duidrj``'s ``sfac *= wj``).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.indices import build_index
from .common import (LANES, SPECIES_CHANNELS, conj_mul, for_each_neighbor,
                     geom_ck_grad, half_prev_rows, level_coefs, level_stitch,
                     pair_spec, plane_spec, resolve_interpret)


def _cm_add(x, y):
    """Elementwise sum of two (re, im) pairs (product-rule accumulation)."""
    return x[0] + y[0], x[1] + y[1]


def _half_level_step(pl_r, pl_i, dpl_r, dpl_i, a, da, b, db, j, dtype):
    """Advance left-rows-only (u, du[3]) one level.

    pl_*: [rows_{j-1}, j, L] left storage of layer j-1.
    Returns left storage of layer j: [j//2+1, j+1, L] (+ tangents).
    The value recursion is exactly :func:`common.u_half_level_step`;
    the tangents apply the product rule d(conj(c) u) = conj(dc) u +
    conj(c) du to each term before the same column stitch."""
    ca, cb, _, _ = level_coefs(j, dtype)
    a_r, a_i = a
    b_r, b_i = b
    da_r, da_i = da
    db_r, db_i = db

    p_r, p_i = half_prev_rows(pl_r, pl_i, j, dtype)
    left_r, left_i = level_stitch(ca, cb, conj_mul(a_r, a_i, p_r, p_i),
                                  conj_mul(b_r, b_i, p_r, p_i))

    dfull_r, dfull_i = [], []
    for k in range(3):
        dp_r, dp_i = half_prev_rows(dpl_r[k], dpl_i[k], j, dtype)
        dau = _cm_add(conj_mul(da_r[k], da_i[k], p_r, p_i),
                      conj_mul(a_r, a_i, dp_r, dp_i))
        dbu = _cm_add(conj_mul(db_r[k], db_i[k], p_r, p_i),
                      conj_mul(b_r, b_i, dp_r, dp_i))
        dl_r, dl_i = level_stitch(ca, cb, dau, dbu)
        dfull_r.append(dl_r)
        dfull_i.append(dl_i)
    return left_r, left_i, dfull_r, dfull_i


def _fused_de_half_kernel(disp_ref, y_r_ref, y_i_ref, out_ref, *, twojmax,
                          nnbor, rcut, rmin0, rfac0, switch_flag, dtype):
    idx = build_index(twojmax)

    def neighbor(k):
        x = disp_ref[k, 0, :]
        y = disp_ref[k, 1, :]
        z = disp_ref[k, 2, :]
        m = disp_ref[k, 3, :]
        rc = disp_ref[k, 4, :] if rcut is None else rcut
        (a_r, a_i, b_r, b_i, sfac), (da_r, da_i, db_r, db_i, dsfac) = \
            geom_ck_grad(x, y, z, rc, rmin0, rfac0, switch_flag)
        sfac = sfac * m
        dsfac = [d * m for d in dsfac]

        u_r = jnp.ones((1, 1, LANES), dtype)
        u_i = jnp.zeros((1, 1, LANES), dtype)
        du_r = [jnp.zeros((1, 1, LANES), dtype) for _ in range(3)]
        du_i = [jnp.zeros((1, 1, LANES), dtype) for _ in range(3)]
        acc = [jnp.zeros((LANES,), dtype) for _ in range(3)]

        def contract(j, u_r, u_i, du_r, du_i, acc):
            """Half layer j of Y is exactly the slice at its block base."""
            base = idx.idxu_half_block[j]
            rows = j // 2 + 1
            n = rows * (j + 1)
            ys_r = y_r_ref[base:base + n, :].reshape(rows, j + 1, LANES)
            ys_i = y_i_ref[base:base + n, :].reshape(rows, j + 1, LANES)
            if j == 0:
                w = jnp.full((1, 1, 1), 0.5, dtype)
            else:
                w = level_coefs(j, dtype)[3][:rows]
            wy_r = w * ys_r
            wy_i = w * ys_i
            out = []
            for d in range(3):
                dU_r = dsfac[d] * u_r + sfac * du_r[d]
                dU_i = dsfac[d] * u_i + sfac * du_i[d]
                out.append(acc[d] + jnp.sum(
                    dU_r * wy_r + dU_i * wy_i, axis=(0, 1)))
            return out

        acc = contract(0, u_r, u_i, du_r, du_i, acc)
        for j in range(1, twojmax + 1):
            u_r, u_i, du_r, du_i = _half_level_step(
                u_r, u_i, du_r, du_i,
                (a_r, a_i), (da_r, da_i), (b_r, b_i), (db_r, db_i),
                j, dtype)
            acc = contract(j, u_r, u_i, du_r, du_i, acc)

        for d in range(3):
            out_ref[k, d, :] = 2.0 * acc[d]
        out_ref[k, 3, :] = jnp.zeros((LANES,), dtype)

    for_each_neighbor(nnbor, neighbor)


def _de_half_call(name, disp, y_r, y_i, twojmax, rcut, rmin0, rfac0,
                  switch_flag, interpret):
    nnbor, channels, natoms_pad = disp.shape
    assert channels == (4 if rcut is not None else SPECIES_CHANNELS)
    assert natoms_pad % LANES == 0
    idx = build_index(twojmax)
    assert y_r.shape == (idx.idxu_half_max, natoms_pad), y_r.shape
    dtype = disp.dtype
    kernel = partial(
        _fused_de_half_kernel, twojmax=twojmax, nnbor=nnbor, rcut=rcut,
        rmin0=rmin0, rfac0=rfac0, switch_flag=switch_flag, dtype=dtype)
    grid = (natoms_pad // LANES,)
    nh = idx.idxu_half_max
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pair_spec(nnbor, channels), plane_spec(nh),
                  plane_spec(nh)],
        out_specs=pair_spec(nnbor),
        out_shape=jax.ShapeDtypeStruct((nnbor, 4, natoms_pad), dtype),
        interpret=resolve_interpret(interpret),
        name=name,
    )(disp, y_r, y_i)


def snap_fused_de_half_pallas(disp, y_r, y_i, *, twojmax, rcut, rmin0=0.0,
                              rfac0=0.99363, switch_flag=True,
                              interpret=None):
    """Same contract as snap_fused_de_pallas, except ``y_r``/``y_i`` are
    **half planes** ``[idxu_half_max, natoms_pad]`` (the native output of
    the half-plane Y kernel); recursion state is half-plane throughout."""
    return _de_half_call('snap_fused_de_half', disp, y_r, y_i, twojmax,
                         rcut, rmin0, rfac0, switch_flag, interpret)


def snap_de_species_pallas(disp, y_r, y_i, *, twojmax, rmin0=0.0,
                           rfac0=0.99363, switch_flag=True, interpret=None):
    """Fused dE of the species path: ``disp`` is [nnbor, 5, natoms_pad]
    (x, y, z, w_j, rcut_ij); otherwise :func:`snap_fused_de_half_pallas`'s
    contract.  Returns [nnbor, 4, natoms_pad] (dE/dr x, y, z, 0)."""
    return _de_half_call('snap_de_species', disp, y_r, y_i, twojmax, None,
                         rmin0, rfac0, switch_flag, interpret)
