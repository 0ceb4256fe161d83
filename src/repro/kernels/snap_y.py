"""Pallas TPU kernels: SNAP compute_Yi (paper Sec. IV adjoint, Sec. VI kernel).

The adjoint accumulation Y[jju] += cg * beta[jjb] * U[src1] * U[src2] is the
one irregular-gather stage of the pipeline.  The GPU implementations balance
it with warp-level work distribution (LAMMPS-KOKKOS, Kokkos-MTP).  The CG
tables are compile-time constants and every atom runs the same table, so
on the TPU the atoms go on lanes and the table is walked entry by entry.

The **half-plane** kernel (:func:`snap_y_half_pallas`, the pipeline's
default) is that walk, on the VPU.  U planes come in as
``[idxu_half_max, natoms_pad]`` (the mirror fold
``u(j,mb,ma) = (-1)^(mb+ma) conj(u(j,j-mb,j-ma))`` is pre-applied to the
COO tables at build time — see ``SnapIndex.z_half_*``).  Per lane block
the kernel packs U and its conjugate into a VMEM scratch in which one U
row of the whole block is a run of whole vregs (lane tiles on sublanes),
then for each table entry reads its two factor rows by dynamic row
offset, forms the complex product in f32, scales it by the entry's
coefficient ``cg * y_fac * beta[y_jjb]`` and sums it.  The table is
sorted by destination and padded so every ``Y_GROUP`` entries share one:
a group sums in registers and touches the Y scratch once.  Table offsets
stream through SMEM one chunk per step of the inner grid axis; the
conjugation signs are folded into the factor rows (a conjugated factor
reads the conj U half of the scratch), so the walk does no sign
arithmetic.  Z is never materialized — the paper's compute_Yi fusion.

The coefficient is formed in the walk.  Its static part ``cg * y_fac``
and the beta index ``y_jjb`` stream with the offsets; beta itself,
``ncoeff`` floats, sits whole in SMEM.  Each entry then costs two more
scalar loads and one scalar multiply, and a call with a fresh beta (the
force call, a fitting loop, a served request) builds no per-entry table
outside the kernel.

:func:`snap_y_species_pallas` (multi-element SNAP) is the same walk
with beta ``[nelements, ncoeff]`` in SMEM and a plane of each lane's
element: each entry forms one coefficient per element and selects it
per lane.

The walk issues the algorithm's 10 flops per entry and atom.  It
replaced a one-hot MXU contraction that issued 170-760x that work at
the 2J=8 and 2J=14 sizes: Y 53.0 and 596 ms an evaluation on one v5e.

The **full-plane** kernel (:func:`snap_y_pallas`, ``layout='full'``, kept
for A/B) is that one-hot contraction:

    Y  =  sum_tiles  S_t @ ((G1_t @ U) * (G2_t @ U))        (complex)

where G1/G2 are [tile, idxu_max] one-hot gather matrices built in-kernel
from int32 index rows (broadcasted-iota compare — no dynamic indexing), and
S folds the scatter destination one-hot with the per-entry coefficient.
Layout: grid = (lane tiles, COO tiles) with the partial-Y accumulator
revisiting its VMEM block across the inner COO axis.  Index tables are
stored ``[ntiles, 1, tile]`` and stream through VMEM one ``[1, tile]`` row
at a time: the unit middle axis makes each block span its array's full
last two dimensions, which Mosaic requires of a block whose sublane extent
(1) is not a multiple of 8.
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
Y_HALF_TILE = 2048   # COO entries per SMEM chunk of the half-layout walk
Y_GROUP = 8          # entries that share a destination, summed in registers
Y_UNROLL = 2         # groups per iteration of the walk's loop
Y_LANE_TILES = 32    # most 128-lane tiles in one lane block of the walk
Y_HALF_VMEM = 64 * 1024 * 1024   # the walk's VMEM budget for its blocks
# scoped-VMEM limit of the full-plane Y kernel.  Mosaic's 16 MiB default is
# too small for it at 2J=14 (two [tile, 1240] one-hots plus the f32
# multi-pass matmul scratch); a v5e core has 128 MiB.  The half-plane walk
# sizes its own limit from its blocks (``_y_half_block``).
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
# half-plane variant: sparse walk over the static CG table on the VPU
# ---------------------------------------------------------------------------

@lru_cache(maxsize=16)
def _y_half_coo_tiles(twojmax: int, tile: int):
    """Half-space COO table in walk order, padded to [ntiles, 1, chunk].

    Entries are sorted by destination and every destination's run is
    padded to a multiple of ``Y_GROUP`` (pad entries: cgfac = 0), so
    each group of ``Y_GROUP`` consecutive entries shares one destination.
    ``chunk`` is ``tile``, or less for a table shorter than that, which
    is then one chunk of whole loop iterations.

    Returns (fac1, fac2, dest, cgfac, jjb): the two factors' rows in the
    walk's factor table ``[U; conj U]`` (``src + idxu_half_max`` where
    the mirror conjugates the factor, σ = -1), the destination of each
    group ``[ntiles, 1, chunk // Y_GROUP]``, the static part of each
    entry's coefficient, mirror-folded CG product times ``y_fac`` (float64),
    and the beta index ``y_jjb`` of its idxz row: the walk's coefficient
    is ``cgfac * beta[jjb]``.
    """
    step = Y_GROUP * Y_UNROLL
    assert tile % step == 0, (tile, step)
    idx = build_index(twojmax)
    nh = idx.idxu_half_max
    order = np.lexsort((idx.z_half_src2, idx.z_half_src1, idx.z_half_dest))
    dest = idx.z_half_dest[order]
    counts = np.bincount(dest, minlength=nh)
    padded = -(-counts // Y_GROUP) * Y_GROUP
    run0 = np.cumsum(padded) - padded          # first slot of each run
    first = np.cumsum(counts) - counts         # first sorted entry of each
    pos = run0[dest] + np.arange(dest.size) - first[dest]
    tile = min(tile, -(-int(padded.sum()) // step) * step)
    ntiles = -(-int(padded.sum()) // tile)

    def place(a, dtype):
        out = np.zeros(ntiles * tile, dtype)
        out[pos] = a[order]
        return out.reshape(ntiles, 1, tile)

    group_dest = np.zeros(ntiles * tile // Y_GROUP, np.int32)
    group_dest[:padded.sum() // Y_GROUP] = np.repeat(np.arange(nh),
                                                      padded // Y_GROUP)
    return (place(idx.z_half_src1 + nh * (idx.z_half_sig1 < 0), np.int32),
            place(idx.z_half_src2 + nh * (idx.z_half_sig2 < 0), np.int32),
            group_dest.reshape(ntiles, 1, tile // Y_GROUP),
            place(idx.z_half_cg * idx.y_fac[idx.z_half_jjz], np.float64),
            place(idx.y_jjb[idx.z_half_jjz], np.int32))


def _y_half_block(nh: int, natoms_pad: int, itemsize: int):
    """(lane tiles per block, sublanes per part, VMEM bytes) of the walk.

    As many 128-lane tiles as fit ``Y_HALF_VMEM``, at most
    ``Y_LANE_TILES``, spread evenly over the blocks: every entry then
    works on whole vregs of as many atoms as the budget allows."""
    tiles = natoms_pad // LANES
    most = Y_LANE_TILES
    while True:
        lane_tiles = -(-tiles // -(-tiles // most))
        rows = -(-lane_tiles // 8) * 8
        # [U; conj U] and Y scratch, Re and Im each; U in and Y out blocks
        need = itemsize * LANES * nh * (6 * rows + 4 * lane_tiles)
        if need <= Y_HALF_VMEM or most == 1:
            return lane_tiles, rows, need
        most //= 2


def _snap_y_half_kernel(*refs, nh, lane_tiles, rows, ngroups, ntiles,
                        ncoeff, dtype, mxu_dtype, nspecies=0):
    """One (lane block, COO chunk) step of the sparse walk.

    Table refs are SMEM scalars of one chunk (offsets already scaled to
    scratch rows), ``beta_ref`` the whole beta in SMEM; ut/y refs are the
    ``[nh, lane_tiles * LANES]`` lane block.  The scratches hold the block
    packed so that one table row is ``2 * rows`` consecutive sublanes, Re
    then Im: lane tile ``c`` of factor ``f`` sits at rows
    ``2 * rows * f + c`` and ``... + rows + c`` (``u_s`` holds U, then
    conj U; ``y_s`` the Y accumulator).  The packing is a strided copy
    once per lane block; every entry then reads and writes whole vregs.

    ``nspecies > 0`` (species path): ``beta_ref`` holds ``nspecies``
    rows of ``ncoeff``, and a ``[1, lane_tiles * LANES]`` element-index
    block comes before the U planes, packed like one U row into ``sp_s``
    (lane tile ``c`` at row ``c``); each entry forms one coefficient per
    element and selects it per lane.
    """
    if nspecies:
        (fac1_ref, fac2_ref, dest_ref, cgfac_ref, jjb_ref, beta_ref, sp_ref,
         ut_r_ref, ut_i_ref, y_r_ref, y_i_ref, u_s, y_s, sp_s) = refs
    else:
        (fac1_ref, fac2_ref, dest_ref, cgfac_ref, jjb_ref, beta_ref,
         ut_r_ref, ut_i_ref, y_r_ref, y_i_ref, u_s, y_s) = refs
    t = pl.program_id(1)
    step = 2 * rows
    rounding = jnp.dtype(mxu_dtype) != jnp.dtype(dtype)

    def rnd(x):
        return x.astype(mxu_dtype).astype(dtype) if rounding else x

    def strided(start):
        return pl.ds(start, nh, stride=step)

    @pl.when(t == 0)
    def _pack():
        for c in range(lane_tiles):
            u_r = rnd(ut_r_ref[:, pl.ds(c * LANES, LANES)])
            u_i = rnd(ut_i_ref[:, pl.ds(c * LANES, LANES)])
            u_s[strided(c), :] = u_r
            u_s[strided(rows + c), :] = u_i
            u_s[strided(nh * step + c), :] = u_r
            u_s[strided(nh * step + rows + c), :] = -u_i
        y_s[...] = jnp.zeros(y_s.shape, dtype)
        if nspecies:
            sp_s[...] = jnp.zeros(sp_s.shape, dtype)
            for c in range(lane_tiles):
                sp_s[pl.ds(c, 1), :] = sp_ref[:, pl.ds(c * LANES, LANES)]

    def factor(off):
        x = u_s[pl.ds(pl.multiple_of(off, step), step), :]
        return x[:rows], x[rows:]

    spv = sp_s[...] if nspecies else None

    def coefficient(k):
        """``cgfac * beta[jjb]`` on the scalar unit (one per element on
        the species path, selected per lane); rounded to ``mxu_dtype``
        as a vector, since the scalar unit has no bfloat16."""
        cg, b = cgfac_ref[k], jjb_ref[k]
        c = cg * beta_ref[b]
        for e in range(1, nspecies):
            c = jnp.where(spv == e, cg * beta_ref[e * ncoeff + b], c)
        if rounding:
            c = rnd(jnp.broadcast_to(c, (rows, LANES)))
        return c

    def group(g):
        acc_r = jnp.zeros((rows, LANES), dtype)
        acc_i = jnp.zeros((rows, LANES), dtype)
        for e in range(Y_GROUP):
            k = g * Y_GROUP + e
            u1r, u1i = factor(fac1_ref[k])
            u2r, u2i = factor(fac2_ref[k])
            c = coefficient(k)
            acc_r = acc_r + c * rnd(u1r * u2r - u1i * u2i)
            acc_i = acc_i + c * rnd(u1r * u2i + u1i * u2r)
        d = pl.multiple_of(dest_ref[g], step)
        y_s[pl.ds(d, rows), :] += acc_r
        y_s[pl.ds(pl.multiple_of(d + rows, rows), rows), :] += acc_i

    def body(i, carry):
        for u in range(Y_UNROLL):
            group(i * Y_UNROLL + u)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(ngroups // Y_UNROLL), body,
                      jnp.int32(0))

    @pl.when(t == ntiles - 1)
    def _unpack():
        for c in range(lane_tiles):
            y_r_ref[:, pl.ds(c * LANES, LANES)] = y_s[strided(c), :]
            y_i_ref[:, pl.ds(c * LANES, LANES)] = y_s[strided(rows + c), :]


@lru_cache(maxsize=16)
def _y_half_tables(twojmax: int, tile: int, step: int, dtype):
    """The walk's SMEM tables: factor and destination rows as scratch row
    offsets (rows times ``step``, the scratch rows one table row spans),
    ``cgfac`` in the plane dtype, and ``jjb``."""
    fac1, fac2, dest, cgfac, jjb = _y_half_coo_tiles(twojmax, tile)
    return fac1 * step, fac2 * step, dest * step, cgfac.astype(dtype), jjb


def _y_half_call(name, ut_r, ut_i, beta, species, twojmax, tile,
                 mxu_dtype, interpret):
    """The walk's ``pallas_call``: ``beta`` is [ncoeff], or (species
    path) [nelements, ncoeff] with the element-index plane ``species``
    [1, natoms_pad]."""
    idx = build_index(twojmax)
    nh, natoms_pad = ut_r.shape
    assert nh == idx.idxu_half_max and natoms_pad % LANES == 0
    ncoeff = beta.shape[-1]
    assert ncoeff == int(idx.y_jjb.max()) + 1, (beta.shape, twojmax)
    dtype = ut_r.dtype
    mxu_dtype = jnp.dtype(mxu_dtype) if mxu_dtype is not None else dtype
    lane_tiles, rows, vmem = _y_half_block(nh, natoms_pad,
                                           jnp.dtype(dtype).itemsize)
    tables = _y_half_tables(twojmax, tile, 2 * rows, jnp.dtype(dtype))
    nspecies = 0 if species is None else beta.shape[0]
    beta = beta.reshape(-1).astype(dtype)

    ntiles, _, chunk = tables[0].shape
    kernel = partial(_snap_y_half_kernel, nh=nh, lane_tiles=lane_tiles,
                     rows=rows, ngroups=chunk // Y_GROUP, ntiles=ntiles,
                     ncoeff=ncoeff, dtype=dtype, mxu_dtype=mxu_dtype,
                     nspecies=nspecies)
    width = lane_tiles * LANES

    def smem(n):
        return pl.BlockSpec((None, None, n), lambda i, t: (t, I0, I0),
                            memory_space=pltpu.SMEM)

    def lanes(n):
        return pl.BlockSpec((n, width), lambda i, t: (I0, i),
                            pipeline_mode=pl.Buffered(1))
    plane = lanes(nh)
    whole = pl.BlockSpec(beta.shape, lambda i, t: (I0,),
                         memory_space=pltpu.SMEM)
    in_specs = [smem(chunk), smem(chunk), smem(chunk // Y_GROUP),
                smem(chunk), smem(chunk), whole, plane, plane]
    operands = [*map(jnp.asarray, tables), beta, ut_r, ut_i]
    scratch = [pltpu.VMEM((2 * nh * 2 * rows, LANES), dtype),
               pltpu.VMEM((nh * 2 * rows, LANES), dtype)]
    if nspecies:
        in_specs.insert(-2, lanes(1))
        operands.insert(-2, species.astype(dtype))
        scratch.append(pltpu.VMEM((rows, LANES), dtype))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(natoms_pad, width), ntiles),
        in_specs=in_specs,
        out_specs=[plane, plane],
        out_shape=[jax.ShapeDtypeStruct((nh, natoms_pad), dtype)] * 2,
        scratch_shapes=scratch,
        interpret=resolve_interpret(interpret),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + 16 * 1024 * 1024),
        name=name,
    )(*operands)


def snap_y_half_pallas(ut_r, ut_i, beta, *, twojmax, tile=Y_HALF_TILE,
                       mxu_dtype=None, interpret=None):
    """ut_r/ut_i: [idxu_half_max, natoms_pad] half Ulisttot planes (self
    included); beta: [ncoeff] linear-model coefficients, cast to the
    plane dtype.

    Returns (y_r, y_i): [idxu_half_max, natoms_pad] adjoint half planes —
    exactly the left rows of :func:`repro.core.bispectrum.compute_ylist`
    on the weighted support (dropped weight-0 middle-row columns are 0).

    mxu_dtype: precision of the contraction's operands (default: the
    plane dtype).  ``jnp.bfloat16`` rounds the U rows, the coefficients
    and the complex products to bfloat16 before they are scaled and
    summed; accumulation stays in the plane dtype.
    """
    return _y_half_call('snap_y_half', ut_r, ut_i, beta, None, twojmax,
                        tile, mxu_dtype, interpret)


def snap_y_species_pallas(ut_r, ut_i, beta, species, *, twojmax,
                          tile=Y_HALF_TILE, mxu_dtype=None, interpret=None):
    """Half-plane Y of the species path: ``beta`` is [nelements, ncoeff]
    and ``species`` [natoms_pad] the element index of every lane.  Each
    lane's Y uses its own element's coefficients, selected per table
    entry (one vector select an element on the walk's ~10 operations an
    entry); otherwise :func:`snap_y_half_pallas`'s contract."""
    natoms_pad = ut_r.shape[1]
    assert species.shape == (natoms_pad,), species.shape
    assert beta.ndim == 2, beta.shape
    return _y_half_call('snap_y_species', ut_r, ut_i, beta,
                        species.reshape(1, natoms_pad), twojmax, tile,
                        mxu_dtype, interpret)
