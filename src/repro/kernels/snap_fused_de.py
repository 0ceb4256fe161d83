"""Pallas TPU kernel: SNAP compute_fused_dE (paper Sec. VI-A).

Fuses compute_dU with the force contraction (eq. 8): for every
(atom, neighbor) pair the kernel

1. recomputes the Wigner recursion from scratch (recompute-over-load, as the
   paper does after eliminating Ulist),
2. carries dual-number tangents (du/dx, du/dy, du/dz) through the recursion
   (the derivative recursion of eq. 9; the paper runs one direction per
   kernel to fit shared memory — VMEM lets us carry all three, documented
   adaptation),
3. contracts each level against Y_j the moment it exists, so neither dU nor
   any per-pair intermediate ever reaches HBM: in goes (x,y,z,mask) + Y,
   out comes dE/dr per pair.  This is the paper's headline memory win
   (0.1 GB / 0.9 GB total footprints).

Layout identical to snap_u: atoms on lanes, neighbors in a rolled loop.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.indices import build_index
from .common import (LANES, for_each_neighbor, geom_ck_grad, level_coefs,
                     pair_spec, plane_spec, resolve_interpret, reverse)


def _dual_level_step(pr, pi, dpr, dpi, a, da, b, db, j, dtype):
    """Advance (u, du[3]) one recursion level.

    pr/pi: [j, j, L] full previous layer; dpr/dpi: lists of 3 such tangents.
    a = (a_r, a_i), da = (da_r[3], da_i[3]); likewise b, db.
    Returns (full_r, full_i, dfull_r[3], dfull_i[3]) at [j+1, j+1, L].
    """
    rows = j // 2 + 1
    ca, cb, sgn, _ = level_coefs(j, dtype)
    nmir = j + 1 - rows
    pad_a = [(0, 0), (0, 1), (0, 0)]
    pad_b = [(0, 0), (1, 0), (0, 0)]
    a_r, a_i = a
    b_r, b_i = b
    da_r, da_i = da
    db_r, db_i = db

    p_r, p_i = pr[:rows], pi[:rows]
    au_r = a_r * p_r + a_i * p_i
    au_i = a_r * p_i - a_i * p_r
    bu_r = b_r * p_r + b_i * p_i
    bu_i = b_r * p_i - b_i * p_r
    left_r = jnp.pad(ca * au_r, pad_a) + jnp.pad(cb * bu_r, pad_b)
    left_i = jnp.pad(ca * au_i, pad_a) + jnp.pad(cb * bu_i, pad_b)
    full_r = jnp.concatenate(
        [left_r, sgn * reverse(reverse(left_r[:nmir], 0), 1)], axis=0)
    full_i = jnp.concatenate(
        [left_i, -sgn * reverse(reverse(left_i[:nmir], 0), 1)], axis=0)

    dfull_r, dfull_i = [], []
    for k in range(3):
        dp_r, dp_i = dpr[k][:rows], dpi[k][:rows]
        # d(conj(a) u) = conj(da) u + conj(a) du
        dau_r = da_r[k] * p_r + da_i[k] * p_i + a_r * dp_r + a_i * dp_i
        dau_i = da_r[k] * p_i - da_i[k] * p_r + a_r * dp_i - a_i * dp_r
        dbu_r = db_r[k] * p_r + db_i[k] * p_i + b_r * dp_r + b_i * dp_i
        dbu_i = db_r[k] * p_i - db_i[k] * p_r + b_r * dp_i - b_i * dp_r
        dl_r = jnp.pad(ca * dau_r, pad_a) + jnp.pad(cb * dbu_r, pad_b)
        dl_i = jnp.pad(ca * dau_i, pad_a) + jnp.pad(cb * dbu_i, pad_b)
        dfull_r.append(jnp.concatenate(
            [dl_r, sgn * reverse(reverse(dl_r[:nmir], 0), 1)], axis=0))
        dfull_i.append(jnp.concatenate(
            [dl_i, -sgn * reverse(reverse(dl_i[:nmir], 0), 1)], axis=0))
    return full_r, full_i, dfull_r, dfull_i


def _fused_de_kernel(disp_ref, y_r_ref, y_i_ref, out_ref, *, twojmax, nnbor,
                     rcut, rmin0, rfac0, switch_flag, dtype):
    """disp_ref: [nnbor, 4, LANES]; y_*_ref: [idxu_max, LANES];
    out_ref: [nnbor, 4, LANES] = dE/d(x,y,z) per pair (row 3 zero)."""
    idx = build_index(twojmax)

    def neighbor(k):
        x = disp_ref[k, 0, :]
        y = disp_ref[k, 1, :]
        z = disp_ref[k, 2, :]
        m = disp_ref[k, 3, :]
        (a_r, a_i, b_r, b_i, sfac), (da_r, da_i, db_r, db_i, dsfac) = \
            geom_ck_grad(x, y, z, rcut, rmin0, rfac0, switch_flag)
        sfac = sfac * m
        dsfac = [d * m for d in dsfac]

        u_r = jnp.ones((1, 1, LANES), dtype)
        u_i = jnp.zeros((1, 1, LANES), dtype)
        du_r = [jnp.zeros((1, 1, LANES), dtype) for _ in range(3)]
        du_i = [jnp.zeros((1, 1, LANES), dtype) for _ in range(3)]
        acc = [jnp.zeros((LANES,), dtype) for _ in range(3)]

        def contract(j, u_r, u_i, du_r, du_i, acc):
            """acc[d] += sum w * Re(conj(dUfinal_d) Y_j) over the layer."""
            base = idx.idxu_block[j]
            n = (j + 1) * (j + 1)
            ys_r = y_r_ref[base:base + n, :].reshape(j + 1, j + 1, LANES)
            ys_i = y_i_ref[base:base + n, :].reshape(j + 1, j + 1, LANES)
            if j == 0:
                w = jnp.full((1, 1, 1), 0.5, dtype)
            else:
                w = level_coefs(j, dtype)[3]
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
            u_r, u_i, du_r, du_i = _dual_level_step(
                u_r, u_i, du_r, du_i,
                (a_r, a_i), (da_r, da_i), (b_r, b_i), (db_r, db_i),
                j, dtype)
            acc = contract(j, u_r, u_i, du_r, du_i, acc)

        for d in range(3):
            out_ref[k, d, :] = 2.0 * acc[d]
        out_ref[k, 3, :] = jnp.zeros((LANES,), dtype)

    for_each_neighbor(nnbor, neighbor)


def snap_fused_de_pallas(disp, y_r, y_i, *, twojmax, rcut, rmin0=0.0,
                         rfac0=0.99363, switch_flag=True, interpret=None):
    """disp: [nnbor, 4, natoms_pad]; y_r/y_i: [idxu_max, natoms_pad].

    Returns dedr: [nnbor, 4, natoms_pad] (rows x, y, z, 0).
    """
    nnbor, four, natoms_pad = disp.shape
    assert four == 4 and natoms_pad % LANES == 0
    idx = build_index(twojmax)
    assert y_r.shape == (idx.idxu_max, natoms_pad)
    dtype = disp.dtype
    kernel = partial(
        _fused_de_kernel, twojmax=twojmax, nnbor=nnbor, rcut=rcut,
        rmin0=rmin0, rfac0=rfac0, switch_flag=switch_flag, dtype=dtype)
    grid = (natoms_pad // LANES,)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pair_spec(nnbor), plane_spec(idx.idxu_max),
                  plane_spec(idx.idxu_max)],
        out_specs=pair_spec(nnbor),
        out_shape=jax.ShapeDtypeStruct((nnbor, 4, natoms_pad), dtype),
        interpret=resolve_interpret(interpret),
        name='snap_fused_de',
    )(disp, y_r, y_i)
