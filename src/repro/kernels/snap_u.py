"""Pallas TPU kernel: SNAP compute_U (paper Sec. VI-A).

Adaptation of the paper's shared-memory recursion kernel:

- one grid step owns a 128-atom lane tile (AoSoA inner "A" = lane width);
- the neighbor sum that needed CUDA atomics becomes a reduction over the
  neighbor axis into the VMEM output block (a rolled in-kernel loop);
- only the previous recursion level is kept live (the paper's double
  buffer) — the full Ulist per pair is never materialized in HBM, only the
  per-atom Ulisttot leaves the kernel;
- re/im are split planes (paper Sec. VI-A split for atomics; here it keeps
  every load/store a full 8x128 tile).

VMEM budget per grid step (2J=14, fp32): inputs nnbor*4*128*4 B (~0.4 MB for
26 neighbors) + 2 output planes 1240*128*4 B (~1.3 MB) + live recursion
state < 0.5 MB — far under the ~128 MB/core budget, leaving room for
multiple in-flight grid steps.

``snap_u_half_pallas`` is the half-plane variant (pipeline default): the
recursion carries only the symmetric left rows 2mb <= j and the output
planes are ``[idxu_half_max, natoms_pad]`` (652 vs 1240 rows at 2J=14) —
the mirror fill disappears from the per-level step entirely and the
emitted HBM plane traffic drops ~1.9x.

``snap_u_species_pallas`` is the half-plane kernel of multi-element SNAP:
its per-pair array has a fifth channel, the pair's cutoff, which the
geometry reads per lane in place of the compiled-in scalar, and its mask
channel carries the neighbour's element weight.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.indices import build_index
from .common import (LANES, SPECIES_CHANNELS, for_each_neighbor, geom_ck,
                     pair_spec, plane_spec, resolve_interpret,
                     u_half_level_step, u_level_step)


def _snap_u_kernel(disp_ref, out_r_ref, out_i_ref, *, level_step, blocks,
                   twojmax, nnbor, rcut, rmin0, rfac0, switch_flag, dtype):
    """disp_ref: [nnbor, 4, LANES] rows (x, y, z, mask) — atoms on lanes;
    with ``rcut=None`` (species path) [nnbor, 5, LANES] rows (x, y, z,
    weight, rcut).
    out_*_ref: [rows, LANES] accumulated sum_k sfac_k * U_k (no self).

    ``level_step`` advances the recursion one level (full planes or left
    rows only) and ``blocks[j]`` is level j's first output row.  Each
    level is added into its row block of the output refs as soon as it
    exists, so only one neighbor's recursion state is ever live (holding
    all levels of all neighbors overruns Mosaic's 16 MiB scoped VMEM at
    2J=14)."""
    out_r_ref[...] = jnp.zeros(out_r_ref.shape, dtype)
    out_i_ref[...] = jnp.zeros(out_i_ref.shape, dtype)

    def neighbor(k):
        x = disp_ref[k, 0, :]
        y = disp_ref[k, 1, :]
        z = disp_ref[k, 2, :]
        m = disp_ref[k, 3, :]
        rc = disp_ref[k, 4, :] if rcut is None else rcut
        a_r, a_i, b_r, b_i, sfac = geom_ck(
            x, y, z, rc, rmin0, rfac0, switch_flag)
        sfac = sfac * m
        out_r_ref[0:1, :] += sfac[None, :]
        lvl_r = jnp.ones((1, 1, LANES), dtype)
        lvl_i = jnp.zeros((1, 1, LANES), dtype)
        for j in range(1, twojmax + 1):
            lvl_r, lvl_i = level_step(lvl_r, lvl_i, a_r, a_i, b_r, b_i, j,
                                      dtype)
            base = int(blocks[j])
            n = lvl_r.shape[0] * lvl_r.shape[1]
            out_r_ref[base:base + n, :] += sfac * lvl_r.reshape(n, LANES)
            out_i_ref[base:base + n, :] += sfac * lvl_i.reshape(n, LANES)

    for_each_neighbor(nnbor, neighbor)


def _u_call(name, disp, rows, level_step, blocks, twojmax, rcut, rmin0,
            rfac0, switch_flag, interpret):
    nnbor, channels, natoms_pad = disp.shape
    assert channels == (4 if rcut is not None else SPECIES_CHANNELS)
    assert natoms_pad % LANES == 0
    dtype = disp.dtype
    kernel = partial(
        _snap_u_kernel, level_step=level_step, blocks=blocks,
        twojmax=twojmax, nnbor=nnbor, rcut=rcut, rmin0=rmin0, rfac0=rfac0,
        switch_flag=switch_flag, dtype=dtype)
    plane = jax.ShapeDtypeStruct((rows, natoms_pad), dtype)
    return pl.pallas_call(
        kernel,
        grid=(natoms_pad // LANES,),
        in_specs=[pair_spec(nnbor, channels)],
        out_specs=[plane_spec(rows), plane_spec(rows)],
        out_shape=[plane, plane],
        interpret=resolve_interpret(interpret),
        name=name,
    )(disp)


def snap_u_pallas(disp, *, twojmax, rcut, rmin0=0.0, rfac0=0.99363,
                  switch_flag=True, interpret=None):
    """disp: [nnbor, 4, natoms_pad] (x, y, z, mask), natoms_pad % 128 == 0.

    Returns (ut_r, ut_i): [idxu_max, natoms_pad], neighbor-accumulated raw
    U sums (self contribution NOT included — added by the ops wrapper).
    """
    idx = build_index(twojmax)
    return _u_call('snap_u', disp, idx.idxu_max, u_level_step,
                   idx.idxu_block, twojmax, rcut, rmin0, rfac0, switch_flag,
                   interpret)


def snap_u_half_pallas(disp, *, twojmax, rcut, rmin0=0.0, rfac0=0.99363,
                       switch_flag=True, interpret=None):
    """Half-plane U: same contract as :func:`snap_u_pallas` but the output
    planes are ``[idxu_half_max, natoms_pad]`` — only the symmetric left
    rows (2mb <= j) ever exist, in HBM or VMEM: the recursion state is
    left-rows-only from the start (no per-level mirror fill at all).  The
    mirrored rows are recoverable through ``SnapIndex.full_to_half``; the
    downstream kernels never need them materialized."""
    idx = build_index(twojmax)
    return _u_call('snap_u_half', disp, idx.idxu_half_max,
                   u_half_level_step, idx.idxu_half_block, twojmax, rcut,
                   rmin0, rfac0, switch_flag, interpret)


def snap_u_species_pallas(disp, *, twojmax, rmin0=0.0, rfac0=0.99363,
                          switch_flag=True, interpret=None):
    """Half-plane U of the species path: ``disp`` is [nnbor, 5,
    natoms_pad] (x, y, z, w_j, rcut_ij) with ``w_j`` 0 on every slot off
    the pair set.  Each pair's theta0 and switching function use its own
    cutoff, and its switching value is scaled by ``w_j``; the output is
    :func:`snap_u_half_pallas`'s."""
    idx = build_index(twojmax)
    return _u_call('snap_u_species', disp, idx.idxu_half_max,
                   u_half_level_step, idx.idxu_half_block, twojmax, None,
                   rmin0, rfac0, switch_flag, interpret)
