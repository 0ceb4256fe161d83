"""Pallas TPU kernel: SNAP compute_Yi (paper Sec. IV adjoint, Sec. VI kernel).

The adjoint accumulation Y[jju] += cg * beta[jjb] * U[src1] * U[src2] is the
one irregular-gather stage of the pipeline.  The GPU implementations balance
it with warp-level work distribution (LAMMPS-KOKKOS, Kokkos-MTP); the TPU
adaptation here turns the static COO Clebsch-Gordan tables into *one-hot
matmuls* so the whole contraction runs on the MXU:

    Y  =  sum_tiles  S_t @ ((G1_t @ U) * (G2_t @ U))        (complex)

where G1/G2 are [tile, idxu_max] one-hot gather matrices built in-kernel
from int32 index rows (broadcasted-iota compare — no dynamic indexing), and
S folds the scatter destination one-hot with the per-entry coefficient
``cg * y_fac * beta[y_jjb]``.  The beta factor is a runtime [nnz] gather
done once at the JAX level (no natoms axis), so the kernel itself is
beta-agnostic and Z is never materialized — the paper's compute_Yi fusion.

Layout: atoms on the 128-wide lane axis ([idxu_max, natoms_pad] planes,
identical to snap_u / snap_fused_de), grid = (lane tiles, COO tiles) with
the partial-Y accumulator revisiting its VMEM block across the inner COO
axis.  Index tables are stored ``[ntiles, 1, tile]`` and stream through
VMEM one ``[1, tile]`` row at a time: the unit middle axis makes each
block span its array's full last two dimensions, which Mosaic requires of
a block whose sublane extent (1) is not a multiple of 8.

The **half-plane** variant (:func:`snap_y_half_pallas`) indexes the
symmetric half space instead: U planes come in as ``[idxu_half_max, L]``
(the mirror fold ``u(j,mb,ma) = (-1)^(mb+ma) conj(u(j,j-mb,j-ma))`` is
pre-applied to the COO tables at build time — see
``SnapIndex.z_half_*``), gathers carry a per-entry ±1 conjugation factor
on the imaginary plane, and the scatter lands in the half space too.
Both one-hot operand axes shrink ~1.9x, so matmul FLOPs, one-hot build
work, and U/Y plane traffic all near-halve; dead destination entries
(weight-0 middle-row columns) are dropped from the COO axis as well.

A ``mxu_dtype`` knob (default: the plane dtype) casts every operand
feeding ``jnp.dot`` — one-hots and U planes on the gather side, the
coefficient-scaled scatter one-hot and the Z products on the scatter
side — while ``preferred_element_type`` keeps accumulation in the plane
dtype.  An f32 feed asks for full-precision (``HIGHEST``) MXU passes.  ``mxu_dtype=jnp.bfloat16`` opens the MXU's native bf16 rate on
the one pipeline stage that is matmul-bound.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.indices import build_index

from .common import I0, LANES, plane_spec, resolve_interpret

Y_TILE = 512   # COO entries per grid step; 128-multiple keeps tiles aligned
# scoped-VMEM limit of the full-plane Y kernel.  Mosaic's 16 MiB default is
# too small for it at 2J=14 (two [tile, 1240] one-hots plus the f32
# multi-pass matmul scratch); a v5e core has 128 MiB.  The half-plane kernel
# fits the default.
Y_VMEM_LIMIT = 48 * 1024 * 1024


@lru_cache(maxsize=16)
def _y_coo_tiles(twojmax: int, tile: int):
    """Static COO tables padded to [ntiles, 1, tile] (pad rows: cg = 0).

    Returns (src1, src2, dest, cg, jjz): flat-u gather indices, flat-u
    scatter destination (idxz -> jju remap already applied), raw CG product,
    and the idxz row of each entry (for the runtime beta gather).
    """
    idx = build_index(twojmax)
    nnz = idx.z_coo_dest.shape[0]
    ntiles = max(1, -(-nnz // tile))
    pad = ntiles * tile - nnz

    def p(a, dtype):
        return np.pad(a, (0, pad)).astype(dtype).reshape(ntiles, 1, tile)

    return (p(idx.z_coo_src1, np.int32),
            p(idx.z_coo_src2, np.int32),
            p(idx.idxz_jju[idx.z_coo_dest], np.int32),
            p(idx.z_coo_cg, np.float64),
            p(idx.z_coo_dest, np.int32))


def _precision(mxu_dtype):
    """Full-precision MXU passes for an f32 (or f64) feed, so the f32
    pipeline keeps its f32 accuracy whatever Mosaic's default pass count
    is; a bf16 feed is one native pass by construction."""
    if jnp.dtype(mxu_dtype) == jnp.bfloat16:
        return None
    return jax.lax.Precision.HIGHEST


def _snap_y_kernel(src1_ref, src2_ref, dest_ref, coef_ref, ut_r_ref, ut_i_ref,
                   y_r_ref, y_i_ref, *, idxu_max, tile, dtype):
    """One (lane tile, COO tile) step of the one-hot-matmul contraction.

    src/dest/coef refs: [1, tile]; ut/y refs: [idxu_max, LANES].
    """
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        y_r_ref[...] = jnp.zeros((idxu_max, LANES), dtype)
        y_i_ref[...] = jnp.zeros((idxu_max, LANES), dtype)

    iu_g = jax.lax.broadcasted_iota(jnp.int32, (tile, idxu_max), 1)
    g1 = (src1_ref[0, :][:, None] == iu_g).astype(dtype)
    g2 = (src2_ref[0, :][:, None] == iu_g).astype(dtype)

    ut_r = ut_r_ref[...]
    ut_i = ut_i_ref[...]
    dot = partial(jnp.dot, preferred_element_type=dtype,
                  precision=_precision(dtype))
    u1r = dot(g1, ut_r)
    u1i = dot(g1, ut_i)
    u2r = dot(g2, ut_r)
    u2i = dot(g2, ut_i)
    prod_r = u1r * u2r - u1i * u2i
    prod_i = u1r * u2i + u1i * u2r

    iu_s = jax.lax.broadcasted_iota(jnp.int32, (idxu_max, tile), 0)
    s = ((dest_ref[0, :][None, :] == iu_s).astype(dtype)
         * coef_ref[0, :][None, :])
    y_r_ref[...] += dot(s, prod_r)
    y_i_ref[...] += dot(s, prod_i)


def y_coef(beta, twojmax: int, tile: int = Y_TILE):
    """Runtime per-COO-entry coefficient ``cg * y_fac * beta[y_jjb]``.

    beta: [idxb_max] global linear-model coefficients.  Returns [ntiles,
    1, tile] in beta's dtype — the only beta-dependent kernel input.
    """
    idx = build_index(twojmax)
    _, _, _, cg, jjz = _y_coo_tiles(twojmax, tile)
    # cast the strong-typed f64 host tables to beta's dtype *before*
    # multiplying: numpy f64 otherwise promotes an f32 beta to f64
    betaj = jnp.asarray(idx.y_fac, beta.dtype) * beta[..., idx.y_jjb]
    return jnp.asarray(cg, beta.dtype) * betaj[..., jjz]


def snap_y_pallas(ut_r, ut_i, coef, *, twojmax, tile=Y_TILE, interpret=None):
    """ut_r/ut_i: [idxu_max, natoms_pad] Ulisttot planes (self included);
    coef: [ntiles, 1, tile] from :func:`y_coef`.

    Returns (y_r, y_i): [idxu_max, natoms_pad] adjoint planes, half-plane
    filled exactly like :func:`repro.core.bispectrum.compute_ylist`.
    """
    idx = build_index(twojmax)
    iu, natoms_pad = ut_r.shape
    assert iu == idx.idxu_max and natoms_pad % LANES == 0
    dtype = ut_r.dtype
    src1, src2, dest, _, _ = _y_coo_tiles(twojmax, tile)
    ntiles = src1.shape[0]
    assert coef.shape == src1.shape, (coef.shape, src1.shape)
    coef = coef.astype(dtype)

    kernel = partial(_snap_y_kernel, idxu_max=idx.idxu_max, tile=tile,
                     dtype=dtype)
    grid = (natoms_pad // LANES, ntiles)
    coo_spec = pl.BlockSpec((None, 1, tile), lambda i, t: (t, I0, I0))
    u_spec = plane_spec(idx.idxu_max)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[coo_spec, coo_spec, coo_spec, coo_spec, u_spec, u_spec],
        out_specs=[u_spec, u_spec],
        out_shape=[
            jax.ShapeDtypeStruct((idx.idxu_max, natoms_pad), dtype),
            jax.ShapeDtypeStruct((idx.idxu_max, natoms_pad), dtype)],
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=Y_VMEM_LIMIT),
        name='snap_y',
    )(jnp.asarray(src1), jnp.asarray(src2), jnp.asarray(dest), coef,
      ut_r, ut_i)


# ---------------------------------------------------------------------------
# half-plane variant
# ---------------------------------------------------------------------------

@lru_cache(maxsize=16)
def _y_half_coo_tiles(twojmax: int, tile: int):
    """Half-space COO tables padded to [ntiles, 1, tile] (pad: cg = 0).

    Returns (src1, src2, sig1, sig2, dest, cg, jjz): half-space gather
    indices, ±1 conjugation factors for the imaginary gathers, half-space
    scatter destination, mirror-folded CG product, and the idxz row of
    each entry (runtime beta gather).
    """
    idx = build_index(twojmax)
    nnz = idx.z_half_dest.shape[0]
    ntiles = max(1, -(-nnz // tile))
    pad = ntiles * tile - nnz

    def p(a, dtype, fill=0):
        return np.pad(a, (0, pad), constant_values=fill) \
            .astype(dtype).reshape(ntiles, 1, tile)

    return (p(idx.z_half_src1, np.int32),
            p(idx.z_half_src2, np.int32),
            p(idx.z_half_sig1, np.float64, 1),
            p(idx.z_half_sig2, np.float64, 1),
            p(idx.z_half_dest, np.int32),
            p(idx.z_half_cg, np.float64),
            p(idx.z_half_jjz, np.int32))


def _snap_y_half_kernel(src1_ref, src2_ref, sig1_ref, sig2_ref, dest_ref,
                        coef_ref, ut_r_ref, ut_i_ref, y_r_ref, y_i_ref, *,
                        idxu_half_max, tile, dtype, mxu_dtype):
    """One (lane tile, COO tile) step on the halved index space.

    The imaginary gathers carry the mirror conjugation as a per-entry ±1
    factor: with u_full = s·conj^c(u_half), writing ṽi = σ·vi (σ = -1
    where c) keeps the complex-multiply form unchanged while s folds
    into the scatter coefficient.  σ is constant along each one-hot row,
    so it is applied *after* the gather matmul on the [tile, LANES]
    result — no signed one-hot copy ever exists — and the body is the
    full kernel's body with two extra [1, tile] sign rows and every
    matmul ~2x smaller.
    """
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        y_r_ref[...] = jnp.zeros((idxu_half_max, LANES), dtype)
        y_i_ref[...] = jnp.zeros((idxu_half_max, LANES), dtype)

    iu_g = jax.lax.broadcasted_iota(jnp.int32, (tile, idxu_half_max), 1)
    g1 = (src1_ref[0, :][:, None] == iu_g).astype(mxu_dtype)
    g2 = (src2_ref[0, :][:, None] == iu_g).astype(mxu_dtype)

    ut_r = ut_r_ref[...].astype(mxu_dtype)
    ut_i = ut_i_ref[...].astype(mxu_dtype)
    dot = partial(jnp.dot, preferred_element_type=dtype,
                  precision=_precision(mxu_dtype))
    v1r = dot(g1, ut_r)
    v1i = dot(g1, ut_i) * sig1_ref[0, :][:, None]   # σ1 · Im(u_half[src1])
    v2r = dot(g2, ut_r)
    v2i = dot(g2, ut_i) * sig2_ref[0, :][:, None]   # σ2 · Im(u_half[src2])
    prod_r = v1r * v2r - v1i * v2i
    prod_i = v1r * v2i + v1i * v2r

    iu_s = jax.lax.broadcasted_iota(jnp.int32, (idxu_half_max, tile), 0)
    s = ((dest_ref[0, :][None, :] == iu_s).astype(dtype)
         * coef_ref[0, :][None, :]).astype(mxu_dtype)
    y_r_ref[...] += dot(s, prod_r.astype(mxu_dtype))
    y_i_ref[...] += dot(s, prod_i.astype(mxu_dtype))


def y_coef_half(beta, twojmax: int, tile: int = Y_TILE):
    """Runtime per-entry coefficient for the half-space COO table:
    ``cg_folded * y_fac * beta[y_jjb]`` — mirror signs s1·s2 are already
    inside ``cg_folded`` (``SnapIndex.z_half_cg``)."""
    idx = build_index(twojmax)
    _, _, _, _, _, cg, jjz = _y_half_coo_tiles(twojmax, tile)
    betaj = jnp.asarray(idx.y_fac, beta.dtype) * beta[..., idx.y_jjb]
    return jnp.asarray(cg, beta.dtype) * betaj[..., jjz]


def snap_y_half_pallas(ut_r, ut_i, coef, *, twojmax, tile=Y_TILE,
                       mxu_dtype=None, interpret=None):
    """ut_r/ut_i: [idxu_half_max, natoms_pad] half Ulisttot planes (self
    included); coef: [ntiles, 1, tile] from :func:`y_coef_half`.

    Returns (y_r, y_i): [idxu_half_max, natoms_pad] adjoint half planes —
    exactly the left rows of :func:`repro.core.bispectrum.compute_ylist`
    on the weighted support (dropped weight-0 middle-row columns are 0).

    mxu_dtype: dtype of the operands fed to ``jnp.dot`` (default: the
    plane dtype).  ``jnp.bfloat16`` halves MXU-feed bytes; accumulation
    stays in the plane dtype via ``preferred_element_type``.
    """
    idx = build_index(twojmax)
    iu, natoms_pad = ut_r.shape
    assert iu == idx.idxu_half_max and natoms_pad % LANES == 0
    dtype = ut_r.dtype
    mxu_dtype = jnp.dtype(mxu_dtype) if mxu_dtype is not None else dtype
    src1, src2, sig1, sig2, dest, _, _ = _y_half_coo_tiles(twojmax, tile)
    ntiles = src1.shape[0]
    assert coef.shape == src1.shape, (coef.shape, src1.shape)
    coef = coef.astype(dtype)

    kernel = partial(_snap_y_half_kernel, idxu_half_max=idx.idxu_half_max,
                     tile=tile, dtype=dtype, mxu_dtype=mxu_dtype)
    grid = (natoms_pad // LANES, ntiles)
    nh = idx.idxu_half_max
    coo_spec = pl.BlockSpec((None, 1, tile), lambda i, t: (t, I0, I0))
    u_spec = plane_spec(nh)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[coo_spec, coo_spec, coo_spec, coo_spec, coo_spec,
                  coo_spec, u_spec, u_spec],
        out_specs=[u_spec, u_spec],
        out_shape=[
            jax.ShapeDtypeStruct((nh, natoms_pad), dtype),
            jax.ShapeDtypeStruct((nh, natoms_pad), dtype)],
        interpret=resolve_interpret(interpret),
        name='snap_y_half',
    )(jnp.asarray(src1), jnp.asarray(src2),
      jnp.asarray(sig1, dtype), jnp.asarray(sig2, dtype),
      jnp.asarray(dest), coef, ut_r, ut_i)
