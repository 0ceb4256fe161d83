"""Shared machinery for the SNAP Pallas TPU kernels.

Layout convention (the TPU adaptation of the paper's Sec. VI-B AoSoA):
the *atom* index lives on the 128-wide lane dimension (innermost "A" = 128),
quantum numbers live on sublanes, and neighbors are iterated inside the
kernel (replacing CUDA atomics with an in-register reduction).

The per-level recursion constants (rootpq coefficient matrices, mirror sign
matrices, half-plane contraction weights) are small static numpy tables baked
into the kernel closure — the analogue of CUDA constant memory.
"""

from __future__ import annotations

import math
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANES = 128
PI = 3.141592653589793
# block indices are int32 even under jax_enable_x64: a Python-int 0 in an
# index map traces as int64 there, which Mosaic cannot lower
I0 = np.int32(0)


def plane_spec(rows):
    """Block of a ``[rows, natoms_pad]`` plane: lane tile ``i`` of the
    (first) grid axis."""
    return pl.BlockSpec((rows, LANES), lambda i, *_: (I0, i))


def pair_spec(nnbor, channels=4):
    """Block of a ``[nnbor, channels, natoms_pad]`` per-pair array: lane
    tile ``i``, every neighbor row."""
    return pl.BlockSpec((nnbor, channels, LANES), lambda i: (I0, I0, i))


# channels of the species path's per-pair array: (x, y, z, w, rcut) — the
# mask channel carries the neighbour's element weight (0 off the pair set)
# and a fifth channel the pair's own cutoff
SPECIES_CHANNELS = 5


@lru_cache(maxsize=8)
def level_consts(twojmax: int):
    """Per-level static tables for the in-kernel Wigner recursion.

    For level j (1..twojmax), left rows mb = 0..j//2:
      CA[mb, ma] =  sqrt((j-ma)/(j-mb))   multiplies conj(a)*u_{j-1}(mb, ma),
                                          contributing to column ma
      CB[mb, ma] = -sqrt((ma+1)/(j-mb))   multiplies conj(b)*u_{j-1}(mb, ma),
                                          contributing to column ma+1
      SGN[r, c]  = (-1)^(mb'+ma') for the mirrored rows mb' = j//2+1 .. j
      W          = half-plane contraction weights over the full layer
    """
    out = []
    for j in range(1, twojmax + 1):
        rows = j // 2 + 1
        ca = np.zeros((rows, j), dtype=np.float64)
        cb = np.zeros((rows, j), dtype=np.float64)
        for mb in range(rows):
            for ma in range(j):
                ca[mb, ma] = math.sqrt((j - ma) / (j - mb))
                cb[mb, ma] = -math.sqrt((ma + 1) / (j - mb))
        nmir = j + 1 - rows
        sgn = np.zeros((nmir, j + 1), dtype=np.float64)
        for r in range(nmir):
            mbp = rows + r
            for ma in range(j + 1):
                sgn[r, ma] = 1.0 if (mbp + ma) % 2 == 0 else -1.0
        w = np.zeros((j + 1, j + 1), dtype=np.float64)
        for mb in range(j + 1):
            if 2 * mb < j:
                w[mb, :] = 1.0
            elif 2 * mb == j:
                w[mb, : j // 2] = 1.0
                w[mb, j // 2] = 0.5
        out.append(dict(j=j, rows=rows, ca=ca, cb=cb, sgn=sgn, w=w))
    return tuple(out)


def iota(dtype, shape, dim):
    """``broadcasted_iota`` in any dtype: Mosaic only builds integer iotas,
    so count in int32 and cast (exact for the small counts used here)."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(dtype)


def reverse(x, axis):
    """``jnp.flip`` along one axis as a concatenation of static unit
    slices: Mosaic has no lowering for ``rev``, and the reversed extents
    here are at most twojmax + 1 rows."""
    n = x.shape[axis]
    return jnp.concatenate(
        [jax.lax.slice_in_dim(x, i, i + 1, axis=axis)
         for i in reversed(range(n))], axis=axis)


def for_each_neighbor(nnbor: int, body):
    """``body(k)`` for k in range(nnbor), as a rolled in-kernel loop.

    Rolled, one neighbor's recursion state is live at a time and the
    compile time does not grow with the neighbor count.  The bounds are
    int32 because under ``jax_enable_x64`` Python-int bounds give an
    int64 induction variable, which Mosaic cannot lower."""
    def step(k, carry):
        body(k)
        return carry
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(nnbor), step, jnp.int32(0))


def level_coefs(j: int, dtype):
    """In-kernel constant builders (Pallas forbids captured trace-time
    constants; iota arithmetic keeps the kernel self-contained).

    Returns CA, CB [rows, j, 1], SGN [nmir, j+1, 1], W [j+1, j+1, 1]."""
    rows = j // 2 + 1
    nmir = j + 1 - rows
    ma = iota(dtype, (rows, j, 1), 1)
    mb = iota(dtype, (rows, j, 1), 0)
    ca = jnp.sqrt((j - ma) / (j - mb))
    cb = -jnp.sqrt((ma + 1.0) / (j - mb))
    r = iota(dtype, (nmir, j + 1, 1), 0)
    c = iota(dtype, (nmir, j + 1, 1), 1)
    sgn = 1.0 - 2.0 * jnp.mod(r + rows + c, 2.0)
    mbw = iota(dtype, (j + 1, j + 1, 1), 0)
    maw = iota(dtype, (j + 1, j + 1, 1), 1)
    # typed branch values: two Python floats would make an f64 array
    # under jax_enable_x64, which Mosaic cannot hold
    half, one, zero = (jnp.asarray(v, dtype) for v in (j / 2.0, 1.0, 0.0))
    w = jnp.where(
        mbw < half, one,
        jnp.where(mbw > half, zero,
                  jnp.where(maw < half, one,
                            jnp.where(maw > half, zero, 0.5 * one))))
    return ca, cb, sgn, w


def u_level_step(prev_r, prev_i, a_r, a_i, b_r, b_i, j, dtype):
    """One recursion level on [rows, cols, LANES] values (pure jnp, usable
    inside a Pallas kernel body).

    prev_*: full previous layer [j, j, L].  Returns full layer [j+1, j+1, L].
    """
    rows = j // 2 + 1
    ca, cb, sgn, _ = level_coefs(j, dtype)
    p_r = prev_r[:rows]            # [rows, j, L]
    p_i = prev_i[:rows]
    left_r, left_i = level_stitch(ca, cb, conj_mul(a_r, a_i, p_r, p_i),
                                  conj_mul(b_r, b_i, p_r, p_i))
    # symmetry fill: u(j-mb, j-ma) -> sign * conj
    nmir = j + 1 - rows
    src_r = reverse(reverse(left_r[:nmir], 0), 1)
    src_i = reverse(reverse(left_i[:nmir], 0), 1)
    full_r = jnp.concatenate([left_r, sgn * src_r], axis=0)
    full_i = jnp.concatenate([left_i, -sgn * src_i], axis=0)
    return full_r, full_i


def mirror_row(row_r, row_i, j_prev, mbp, dtype):
    """Reconstruct row mb'=mbp of a full layer j_prev from its mirror
    source row (left storage).  row_*: [cols, L] source row ALREADY
    selected (row j_prev - mbp reversed by caller).  Applies the
    (-1)^(mb'+ma') conj transform."""
    cols = j_prev + 1
    ma = iota(dtype, (cols, 1), 0)
    sgn = 1.0 - 2.0 * jnp.mod(ma + mbp, 2.0)
    return sgn * row_r, -sgn * row_i


def half_prev_rows(left_r, left_i, j, dtype):
    """Rows 0..j//2 of full layer j-1, given left storage of layer j-1
    (rows 0..(j-1)//2).  For even j appends the one mirrored row."""
    if j % 2 == 1:
        return left_r, left_i
    jp = j - 1
    src_r = reverse(left_r[j // 2 - 1], 0)
    src_i = reverse(left_i[j // 2 - 1], 0)
    mr, mi = mirror_row(src_r, src_i, jp, j // 2, dtype)
    return (jnp.concatenate([left_r, mr[None]], axis=0),
            jnp.concatenate([left_i, mi[None]], axis=0))


def conj_mul(c_r, c_i, p_r, p_i):
    """conj(c) * p on split re/im planes."""
    return c_r * p_r + c_i * p_i, c_r * p_i - c_i * p_r


def level_stitch(ca, cb, au, bu):
    """Column-stitch of one recursion level: the conj(a)-term feeds
    column ma, the conj(b)-term column ma+1, weighted by the rootpq
    coefficient matrices.  au/bu: (re, im) pairs [rows, j, L]; returns
    the new left rows [rows, j+1, L]."""
    pad_a = [(0, 0), (0, 1), (0, 0)]
    pad_b = [(0, 0), (1, 0), (0, 0)]
    (au_r, au_i), (bu_r, bu_i) = au, bu
    return (jnp.pad(ca * au_r, pad_a) + jnp.pad(cb * bu_r, pad_b),
            jnp.pad(ca * au_i, pad_a) + jnp.pad(cb * bu_i, pad_b))


def u_half_level_step(left_r, left_i, a_r, a_i, b_r, b_i, j, dtype):
    """One recursion level on left-rows-only storage (no mirror fill).

    left_*: [ (j-1)//2 + 1, j, L ] left storage of layer j-1.  Returns the
    left storage of layer j: [j//2 + 1, j+1, L].  Identical values to the
    left rows of :func:`u_level_step` — the recursion only ever reads the
    previous layer's rows mb <= j//2 (one of which is mirror-reconstructed
    for even j).
    """
    ca, cb, _, _ = level_coefs(j, dtype)
    p_r, p_i = half_prev_rows(left_r, left_i, j, dtype)
    return level_stitch(ca, cb, conj_mul(a_r, a_i, p_r, p_i),
                        conj_mul(b_r, b_i, p_r, p_i))


def geom_ck(x, y, z, rcut, rmin0, rfac0, switch_flag):
    """Cayley-Klein parameters + sfac, elementwise on lane vectors."""
    rsq = x * x + y * y + z * z
    r = jnp.sqrt(rsq)
    rscale0 = rfac0 * PI / (rcut - rmin0)
    theta0 = (r - rmin0) * rscale0
    z0 = r * jnp.cos(theta0) / jnp.sin(theta0)
    r0inv = 1.0 / jnp.sqrt(rsq + z0 * z0)
    a_r, a_i = r0inv * z0, -r0inv * z
    b_r, b_i = r0inv * y, -r0inv * x
    if switch_flag:
        t = (r - rmin0) * PI / (rcut - rmin0)
        sfac = jnp.where(r <= rmin0, 1.0,
                         jnp.where(r > rcut, 0.0, 0.5 * (jnp.cos(t) + 1.0)))
    else:
        sfac = jnp.ones_like(r)
    return a_r, a_i, b_r, b_i, sfac


def geom_ck_grad(x, y, z, rcut, rmin0, rfac0, switch_flag):
    """Geometry + per-direction derivatives, tuple-of-lanes form.

    Returns (a_r, a_i, b_r, b_i, sfac), and per direction k in (x, y, z):
    lists da_r[k], da_i[k], db_r[k], db_i[k], dsfac[k].
    """
    rsq = x * x + y * y + z * z
    r = jnp.sqrt(rsq)
    rscale0 = rfac0 * PI / (rcut - rmin0)
    theta0 = (r - rmin0) * rscale0
    cs, sn = jnp.cos(theta0), jnp.sin(theta0)
    z0 = r * cs / sn
    dz0dr = z0 / r - r * rscale0 * (rsq + z0 * z0) / rsq
    r0inv = 1.0 / jnp.sqrt(rsq + z0 * z0)
    dr0invdr = -(r0inv ** 3) * (r + z0 * dz0dr)
    unit = (x / r, y / r, z / r)
    a_r, a_i = r0inv * z0, -r0inv * z
    b_r, b_i = r0inv * y, -r0inv * x
    da_r, da_i, db_r, db_i, dsfac = [], [], [], [], []
    if switch_flag:
        c = PI / (rcut - rmin0)
        t = (r - rmin0) * c
        sfac = jnp.where(r <= rmin0, 1.0,
                         jnp.where(r > rcut, 0.0, 0.5 * (jnp.cos(t) + 1.0)))
        dsf = jnp.where((r <= rmin0) | (r > rcut), 0.0, -0.5 * jnp.sin(t) * c)
    else:
        sfac = jnp.ones_like(r)
        dsf = jnp.zeros_like(r)
    for k in range(3):
        dr0inv = dr0invdr * unit[k]
        dz0 = dz0dr * unit[k]
        dar = dz0 * r0inv + z0 * dr0inv
        dai = -z * dr0inv - (r0inv if k == 2 else 0.0)
        dbr = y * dr0inv + (r0inv if k == 1 else 0.0)
        dbi = -x * dr0inv - (r0inv if k == 0 else 0.0)
        da_r.append(dar)
        da_i.append(dai)
        db_r.append(dbr)
        db_i.append(dbi)
        dsfac.append(dsf * unit[k])
    return (a_r, a_i, b_r, b_i, sfac), (da_r, da_i, db_r, db_i, dsfac)


def pad_lanes(arr, axis=-1, lanes=LANES):
    """Pad an axis up to a multiple of the lane width."""
    n = arr.shape[axis]
    pad = (-n) % lanes
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths)


def default_interpret() -> bool:
    """Pallas interpret mode on the CPU backend (the tests), compiled
    Mosaic kernels on a TPU; any other platform is an error rather than a
    silent fall back to the interpreter."""
    platform = jax.devices()[0].platform
    if platform == 'cpu':
        return True
    if platform == 'tpu':
        return False
    raise RuntimeError(f'SNAP Pallas kernels run on tpu (compiled) or cpu '
                       f'(interpreted); found platform {platform!r}')


def resolve_interpret(interpret):
    """``interpret`` as given, or :func:`default_interpret` when None."""
    return default_interpret() if interpret is None else bool(interpret)
