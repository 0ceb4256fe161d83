"""Pure-jnp oracles for the SNAP Pallas kernels.

Each ``ref_*`` mirrors the corresponding kernel's contract exactly (same
input layout, same outputs) but is built from the independently-validated
:mod:`repro.core` reference pipeline — itself cross-checked against
reverse-mode autodiff.  Kernel tests sweep shapes/dtypes and assert_allclose
against these.

The species kernels take the five-channel per-pair array (x, y, z, w_j,
rcut_ij); the oracles take it too, with ``rcut=None``: each pair's cutoff
is then its fifth channel and its weight the mask channel.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core import bispectrum as bs
from repro.core.geometry import (PairGeom, compute_geometry,
                                 compute_geometry_grad)
from repro.core.indices import build_index
from repro.core.ulist import compute_dulist, compute_ulist


def _geom_from_disp(disp, rcut, rmin0, rfac0, switch_flag, grad):
    """disp: [nnbor, 4, natoms] kernel layout -> per-pair geometry
    [natoms, nnbor] with masked sfac/dsfac; with ``rcut=None`` disp is
    [nnbor, 5, natoms] and channel 4 is each pair's cutoff."""
    if rcut is None:
        rcut = disp[:, 4, :].T
    x = disp[:, 0, :].T
    y = disp[:, 1, :].T
    z = disp[:, 2, :].T
    m = disp[:, 3, :].T
    kw = dict(rcut=rcut, rmin0=rmin0, rfac0=rfac0, switch_flag=switch_flag)
    if grad:
        geom, dgeom = compute_geometry_grad(x, y, z, **kw)
        dgeom = dgeom._replace(dsfac=dgeom.dsfac * m[..., None])
    else:
        geom, dgeom = compute_geometry(x, y, z, **kw), None
    geom = geom._replace(sfac=geom.sfac * m)
    return geom, dgeom


def ref_snap_u(disp, *, twojmax, rcut=None, rmin0=0.0, rfac0=0.99363,
               switch_flag=True):
    """Oracle for snap_u_pallas: [nnbor,4,N] -> (ut_r, ut_i) [idxu, N]
    (and for snap_u_species_pallas, full planes, with ``rcut=None``)."""
    idx = build_index(twojmax)
    dtype = disp.dtype
    geom, _ = _geom_from_disp(disp, rcut, rmin0, rfac0, switch_flag, False)
    u = compute_ulist(geom, idx, dtype)                 # [N, nnbor, idxu]
    tot = jnp.sum(u * geom.sfac[..., None].astype(u.dtype), axis=1)
    return tot.real.T.astype(dtype), tot.imag.T.astype(dtype)


def ref_snap_fused_de(disp, y_r, y_i, *, twojmax, rcut=None, rmin0=0.0,
                      rfac0=0.99363, switch_flag=True):
    """Oracle for snap_fused_de_pallas.

    disp: [nnbor, 4, N] (or [nnbor, 5, N] with ``rcut=None``, the species
    kernel's); y_*: [idxu, N].  Returns [nnbor, 4, N].
    """
    idx = build_index(twojmax)
    dtype = disp.dtype
    geom, dgeom = _geom_from_disp(disp, rcut, rmin0, rfac0, switch_flag,
                                  True)
    _, du = compute_dulist(geom, dgeom, idx, dtype)     # [N, nnbor, 3, idxu]
    y = (y_r + 1j * y_i).T.astype(du.dtype)             # [N, idxu]
    w = idx.dedr_weight
    s = (du.real * (w * y.real)[:, None, None, :]
         + du.imag * (w * y.imag)[:, None, None, :])
    dedr = 2.0 * jnp.sum(s, axis=-1)                    # [N, nnbor, 3]
    out = jnp.concatenate(
        [dedr, jnp.zeros(dedr.shape[:2] + (1,), dtype)], axis=-1)
    return out.transpose(1, 2, 0).astype(dtype)
