"""Pallas kernel validation: shape/dtype sweeps against the jnp oracles.

Kernels run in interpret mode on CPU (the container has no TPU); the kernel
*structure* (BlockSpec tiling, lane layout, static slices only) is written
for TPU lowering.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bispectrum as bs
from repro.core.indices import build_index
from repro.core.snap import (SnapConfig, _pair_geometry,
                             energy_forces_adjoint, energy_forces_autodiff)
from repro.core.ulist import compute_ulist, compute_ulisttot
from repro.kernels import snap_y
from repro.kernels.ops import (_kernel_layout, energy_forces_kernel,
                               half_planes_to_full, snap_dedr_kernel,
                               snap_force_pipeline, snap_ui_kernel,
                               snap_yi_kernel)
from repro.kernels.ref import ref_snap_fused_de, ref_snap_u
from repro.kernels.snap_fused_de import snap_fused_de_pallas
from repro.kernels.snap_u import snap_u_half_pallas, snap_u_pallas

from conftest import make_cluster

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.float64: dict(rtol=1e-12, atol=1e-12)}


def _layout(cfg, natoms, nnbor, seed, dtype):
    _, disp, nbr_idx, mask, _ = make_cluster(natoms=natoms, nnbor=nnbor,
                                             seed=seed, rcut=cfg.rcut)
    d, ok, n = _kernel_layout(
        cfg, jnp.asarray(disp[..., 0]), jnp.asarray(disp[..., 1]),
        jnp.asarray(disp[..., 2]), jnp.asarray(mask), dtype)
    return d, disp, nbr_idx, mask


@pytest.mark.parametrize('twojmax', [2, 4, 8])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.float64])
@pytest.mark.parametrize('natoms,nnbor', [(5, 4), (130, 8)])
def test_snap_u_kernel_sweep(twojmax, dtype, natoms, nnbor):
    cfg = SnapConfig(twojmax=twojmax, rcut=3.0)
    d, *_ = _layout(cfg, natoms, nnbor, seed=twojmax + natoms, dtype=dtype)
    kr, ki = snap_u_pallas(d, twojmax=twojmax, rcut=cfg.rcut, interpret=True)
    rr, ri = ref_snap_u(d, twojmax=twojmax, rcut=cfg.rcut)
    np.testing.assert_allclose(np.asarray(kr), np.asarray(rr), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(ki), np.asarray(ri), **TOL[dtype])


@pytest.mark.parametrize('twojmax', [2, 4, 8])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.float64])
@pytest.mark.parametrize('natoms,nnbor', [(5, 4), (130, 8)])
def test_fused_de_kernel_sweep(twojmax, dtype, natoms, nnbor):
    cfg = SnapConfig(twojmax=twojmax, rcut=3.0)
    d, *_ = _layout(cfg, natoms, nnbor, seed=7 * twojmax + natoms,
                    dtype=dtype)
    rng = np.random.default_rng(twojmax)
    shape = (cfg.index.idxu_max, d.shape[-1])
    yr = jnp.asarray(rng.normal(size=shape), dtype)
    yi = jnp.asarray(rng.normal(size=shape), dtype)
    k = snap_fused_de_pallas(d, yr, yi, twojmax=twojmax, rcut=cfg.rcut,
                             interpret=True)
    r = ref_snap_fused_de(d, yr, yi, twojmax=twojmax, rcut=cfg.rcut)
    scale = max(1.0, float(jnp.abs(r).max()))
    np.testing.assert_allclose(np.asarray(k) / scale, np.asarray(r) / scale,
                               **TOL[dtype])


@pytest.mark.parametrize('twojmax', [2, 4, 8])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.float64])
def test_snap_u_half_kernel_sweep(twojmax, dtype):
    """Half-plane U == the left rows of the full oracle; the mirror
    expansion of the half planes reproduces the full oracle everywhere."""
    cfg = SnapConfig(twojmax=twojmax, rcut=3.0)
    idx = cfg.index
    d, *_ = _layout(cfg, 9, 6, seed=3 * twojmax + 1, dtype=dtype)
    hr, hi = snap_u_half_pallas(d, twojmax=twojmax, rcut=cfg.rcut,
                                interpret=True)
    rr, ri = ref_snap_u(d, twojmax=twojmax, rcut=cfg.rcut)
    np.testing.assert_allclose(np.asarray(hr),
                               np.asarray(rr)[idx.half_to_full],
                               **TOL[dtype])
    np.testing.assert_allclose(np.asarray(hi),
                               np.asarray(ri)[idx.half_to_full],
                               **TOL[dtype])
    fr, fi = half_planes_to_full(cfg, hr, hi)
    np.testing.assert_allclose(np.asarray(fr), np.asarray(rr), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(fi), np.asarray(ri), **TOL[dtype])


def _oracle_ulisttot(cfg, disp, mask):
    """fp64 Ulisttot [natoms, idxu_max] from the core reference pipeline."""
    idx = cfg.index
    dx, dy, dz = (jnp.asarray(disp[..., i]) for i in range(3))
    geom, _, ok = _pair_geometry(cfg, dx, dy, dz, jnp.asarray(mask),
                                 grad=False)
    u = compute_ulist(geom, idx, jnp.complex128)
    return compute_ulisttot(u, geom.sfac, ok, idx, cfg.wself)


def _y_parity_cases():
    """(dtype, twojmax, layout, natoms, most lane tiles a block): 9 atoms
    fill one lane tile; 300 and 1,100 atoms split the half walk into
    several lane blocks, the last one partial."""
    cases = []
    for dname, dtype in (('float32', jnp.float32), ('float64', jnp.float64)):
        for twojmax in (4, 8):
            for layout in ('half', 'full'):
                cases.append(pytest.param(dtype, twojmax, layout, 9, None,
                                          id=f'{dname}-{twojmax}-{layout}'))
            for natoms, most in ((300, 2), (1100, 8)):
                cases.append(pytest.param(
                    dtype, twojmax, 'half', natoms, most,
                    id=f'{dname}-{twojmax}-half-n{natoms}'))
    return cases


@pytest.mark.parametrize('dtype,twojmax,layout,natoms,most',
                         _y_parity_cases())
def test_snap_y_kernel_parity(dtype, twojmax, layout, natoms, most,
                              monkeypatch):
    """Pallas Y == bs.compute_ylist on identical Ulisttot.

    Acceptance bar: <= 1e-5 relative (f32) / 1e-10 (f64) at twojmax=8.
    The half layout is compared on the weighted support (dedr_weight > 0):
    it drops the COO entries scattering into weight-0 positions that no
    contraction ever reads, so those read back 0 instead of the reference
    value; the full layout matches everywhere.  ``most`` caps the half
    walk's lane tiles a block, so that 300 atoms (3 tiles) run as blocks
    of 2 + 1 and 1,100 atoms (9 tiles) as 5 + 4.
    """
    if most is not None:
        monkeypatch.setattr(snap_y, 'Y_LANE_TILES', most)
    cfg = SnapConfig(twojmax=twojmax, rcut=3.0)
    _, disp, _, mask, _ = make_cluster(natoms=natoms, nnbor=6, seed=twojmax)
    ut = _oracle_ulisttot(cfg, disp, mask)
    rng = np.random.default_rng(twojmax)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    y_ref = np.asarray(bs.compute_ylist(ut, beta, cfg.index))
    y_k = np.asarray(snap_yi_kernel(cfg, ut, beta, dtype=dtype,
                                    interpret=True, layout=layout))
    if layout == 'half':
        sup = cfg.index.dedr_weight > 0
        y_ref, y_k = y_ref[:, sup], y_k[:, sup]
    scale = max(1.0, float(np.abs(y_ref).max()))
    tol = 1e-5 if dtype == jnp.float32 else 1e-10
    np.testing.assert_allclose(y_k.real / scale, y_ref.real / scale,
                               atol=tol)
    np.testing.assert_allclose(y_k.imag / scale, y_ref.imag / scale,
                               atol=tol)


@pytest.mark.parametrize('batch_beta', [False, True],
                         ids=['shared-beta', 'beta-per-lane'])
def test_snap_y_half_kernel_vmap_matches_solo(batch_beta):
    """A vmapped batch of half Y calls (``ForceServer``'s buckets) equals
    the solo calls bit for bit, with one beta for the batch or, as
    ``ForceServer`` feeds it, one beta per batch lane."""
    twojmax, natoms_pad = 4, 256
    nh = build_index(twojmax).idxu_half_max
    ncoeff = SnapConfig(twojmax=twojmax).ncoeff
    rng = np.random.default_rng(3)
    ur, ui = (jnp.asarray(rng.normal(size=(3, nh, natoms_pad)))
              for _ in range(2))
    beta = jnp.asarray(rng.normal(size=(3, ncoeff)))

    def y(a, b, c):
        return snap_y.snap_y_half_pallas(a, b, c, twojmax=twojmax,
                                         interpret=True)
    if batch_beta:
        batch = jax.vmap(y)(ur, ui, beta)
    else:
        beta = jnp.broadcast_to(beta[0], beta.shape)
        batch = jax.vmap(y, in_axes=(0, 0, None))(ur, ui, beta[0])
    for i in range(3):
        for got, want in zip(batch, y(ur[i], ui[i], beta[i])):
            np.testing.assert_array_equal(np.asarray(got[i]),
                                          np.asarray(want))


def _y_half_rounded(idx, u, beta, mxu_dtype):
    """float64 sum of the half walk's terms, each operand rounded as the
    walk rounds it: U rows, each coefficient ``cg * y_fac * beta[y_jjb]``
    formed in float32 from the index tables, and each complex product
    formed in float32 from the rounded rows.  u: complex [nh, natoms]."""
    def rnd(x):
        return np.asarray(jnp.asarray(x, mxu_dtype).astype(jnp.float32))
    f32 = np.float32
    u_r, u_i = rnd(u.real.astype(f32)), rnd(u.imag.astype(f32))
    jjz = idx.z_half_jjz
    coef = rnd((idx.z_half_cg * idx.y_fac[jjz]).astype(f32)
               * np.asarray(beta, f32)[idx.y_jjb[jjz]])
    s1, s2 = idx.z_half_sig1.astype(f32), idx.z_half_sig2.astype(f32)
    a_r, a_i = u_r[idx.z_half_src1], s1[:, None] * u_i[idx.z_half_src1]
    b_r, b_i = u_r[idx.z_half_src2], s2[:, None] * u_i[idx.z_half_src2]
    p_r, p_i = rnd(a_r * b_r - a_i * b_i), rnd(a_r * b_i + a_i * b_r)
    y = np.zeros(u.shape, np.complex128)
    np.add.at(y, idx.z_half_dest,
              coef[:, None].astype(np.float64) * (p_r + 1j * p_i))
    return y


def test_snap_y_half_bf16_walk_rounds_rows_coefficients_products():
    """The bfloat16 walk (``mxu_dtype``, the benchmark's control) equals,
    to float32 accumulation, the sum of its terms with the U rows, the
    coefficients formed in the kernel and the complex products each
    rounded to bfloat16; and that sum is far from the float32 walk's."""
    twojmax, natoms = 8, 200
    idx = build_index(twojmax)
    nh = idx.idxu_half_max
    rng = np.random.default_rng(8)
    u = rng.normal(size=(nh, natoms)) + 1j * rng.normal(size=(nh, natoms))
    beta = rng.normal(size=SnapConfig(twojmax=twojmax).ncoeff)
    pad = [(0, 0), (0, 256 - natoms)]
    planes = [jnp.asarray(np.pad(x, pad), jnp.float32)
              for x in (u.real, u.imag)]

    def walk(mxu_dtype):
        yr, yi = snap_y.snap_y_half_pallas(
            *planes, jnp.asarray(beta, jnp.float32), twojmax=twojmax,
            mxu_dtype=mxu_dtype, interpret=True)
        return np.asarray(yr)[:, :natoms] + 1j * np.asarray(yi)[:, :natoms]
    want = _y_half_rounded(idx, u, beta, jnp.bfloat16)
    scale = float(np.abs(want).max())
    got = walk(jnp.bfloat16)
    np.testing.assert_allclose(got.real / scale, want.real / scale,
                               atol=2e-6)
    np.testing.assert_allclose(got.imag / scale, want.imag / scale,
                               atol=2e-6)
    assert np.abs(walk(None) - want).max() > 1e-4 * scale


def test_snap_y_kernel_tile_sweep():
    """Tile size must not change the contraction (pad entries are inert)."""
    cfg = SnapConfig(twojmax=4, rcut=3.0)
    _, disp, _, mask, _ = make_cluster(natoms=5, nnbor=4, seed=11)
    ut = _oracle_ulisttot(cfg, disp, mask)
    rng = np.random.default_rng(11)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    ys = [np.asarray(snap_yi_kernel(cfg, ut, beta, dtype=jnp.float64,
                                    interpret=True, y_tile=tile))
          for tile in (128, 512, 2048)]
    np.testing.assert_allclose(ys[1], ys[0], rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(ys[2], ys[0], rtol=1e-12, atol=1e-12)


def test_kernel_pipeline_matches_autodiff():
    """End-to-end zero-relayout pipeline vs the reverse-mode AD oracle."""
    cfg = SnapConfig(twojmax=4, rcut=3.0)
    pos, disp, nbr_idx, mask, shifts = make_cluster(seed=5)
    rng = np.random.default_rng(5)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    e_g, f_g = energy_forces_autodiff(cfg, beta, 0.1, jnp.asarray(pos),
                                      nbr_idx, shifts, mask)
    e_k, _, f_k = snap_force_pipeline(cfg, beta, 0.1, disp[..., 0],
                                      disp[..., 1], disp[..., 2], nbr_idx,
                                      mask, dtype=jnp.float64,
                                      interpret=True)
    np.testing.assert_allclose(float(e_k), float(e_g), rtol=1e-11)
    scale = float(jnp.abs(f_g).max())
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_g),
                               atol=1e-10 * scale)


@pytest.mark.parametrize('layout', ['half', 'full'])
@pytest.mark.parametrize('twojmax', [4, 8])
def test_kernel_pipeline_matches_adjoint(twojmax, layout):
    """End-to-end zero-relayout pipeline == fp64 adjoint, both layouts."""
    cfg = SnapConfig(twojmax=twojmax, rcut=3.0)
    _, disp, nbr_idx, mask, _ = make_cluster(natoms=12, nnbor=8,
                                             seed=twojmax)
    rng = np.random.default_rng(1)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    dx, dy, dz = disp[..., 0], disp[..., 1], disp[..., 2]
    e_ref, _, f_ref = energy_forces_adjoint(cfg, beta, 0.2, dx, dy, dz,
                                            nbr_idx, mask)
    e_k, _, f_k = energy_forces_kernel(cfg, beta, 0.2, dx, dy, dz, nbr_idx,
                                       mask, dtype=jnp.float64,
                                       interpret=True, layout=layout)
    np.testing.assert_allclose(float(e_k), float(e_ref), rtol=1e-11)
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_ref),
                               atol=1e-10 * float(jnp.abs(f_ref).max()))
    # fp32 stays within engineering tolerance of the fp64 oracle
    e_32, _, f_32 = energy_forces_kernel(cfg, beta, 0.2, dx, dy, dz,
                                         nbr_idx, mask, dtype=jnp.float32,
                                         interpret=True, layout=layout)
    rel = float(jnp.abs(f_32 - f_ref).max() / jnp.abs(f_ref).max())
    assert rel < 5e-5, rel


def test_kernel_pipeline_mxu_bf16():
    """bf16 feed policy: the Y contraction's U rows, coefficients and
    products rounded to bfloat16, accumulation in f32 — forces within
    1e-2 relative of the fp64 adjoint, energy too (the acceptance bar for
    the low-precision knob), and further off than the f32 pipeline."""
    cfg = SnapConfig(twojmax=8, rcut=3.0)
    _, disp, nbr_idx, mask, _ = make_cluster(natoms=12, nnbor=8, seed=8)
    rng = np.random.default_rng(1)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    dx, dy, dz = disp[..., 0], disp[..., 1], disp[..., 2]
    e_ref, _, f_ref = energy_forces_adjoint(cfg, beta, 0.2, dx, dy, dz,
                                            nbr_idx, mask)
    e_b, _, f_b = energy_forces_kernel(cfg, beta, 0.2, dx, dy, dz, nbr_idx,
                                       mask, dtype=jnp.float32,
                                       interpret=True,
                                       mxu_dtype=jnp.bfloat16)
    rel = float(jnp.abs(f_b - f_ref).max() / jnp.abs(f_ref).max())
    assert rel < 1e-2, rel
    assert abs(float(e_b) - float(e_ref)) < 1e-2 * abs(float(e_ref)), \
        (float(e_b), float(e_ref))
    _, _, f_32 = energy_forces_kernel(cfg, beta, 0.2, dx, dy, dz, nbr_idx,
                                      mask, dtype=jnp.float32,
                                      interpret=True)
    rel_32 = float(jnp.abs(f_32 - f_ref).max() / jnp.abs(f_ref).max())
    assert rel > rel_32, (rel, rel_32)


@pytest.mark.parametrize('dtype,tol', [(jnp.float32, 1e-5),
                                       (jnp.float64, 1e-10)])
def test_kernel_pipeline_2j14_matches_autodiff(dtype, tol):
    """The paper's 2J=14 problem (configs/snap_2j14): half-plane pipeline
    forces vs the reverse-mode AD oracle at the acceptance bars.

    Small cluster + a large Y tile keep the interpret-mode grid tractable
    (the 2J=14 half COO table is ~1.06M entries)."""
    from repro.configs.snap_2j14 import CONFIG
    cfg = SnapConfig(twojmax=CONFIG['snap'].twojmax, rcut=3.0)
    assert cfg.twojmax == 14
    pos, disp, nbr_idx, mask, shifts = make_cluster(natoms=4, nnbor=3,
                                                    seed=14)
    rng = np.random.default_rng(14)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff) * 1e-2)
    e_g, f_g = energy_forces_autodiff(cfg, beta, 0.1, jnp.asarray(pos),
                                      nbr_idx, shifts, mask)
    e_k, _, f_k = snap_force_pipeline(cfg, beta, 0.1, disp[..., 0],
                                      disp[..., 1], disp[..., 2], nbr_idx,
                                      mask, dtype=dtype, interpret=True,
                                      y_tile=16384)
    scale = float(jnp.abs(f_g).max())
    rel = float(jnp.abs(f_k - f_g).max()) / scale
    assert rel < tol, rel
    np.testing.assert_allclose(float(e_k), float(e_g),
                               rtol=max(tol, 1e-11))


def _gather_sizes(fn, *args):
    """(element counts of every gather's output, number of Pallas calls)
    in ``fn``'s jaxpr, its kernels' bodies included."""
    from repro.analysis.jaxpr_passes import iter_eqns
    eqns = [eqn for eqn, _ in iter_eqns(jax.make_jaxpr(fn)(*args))]
    return ([int(np.prod(v.aval.shape)) for eqn in eqns
             if eqn.primitive.name == 'gather' for v in eqn.outvars],
            sum(eqn.primitive.name == 'pallas_call' for eqn in eqns))


@pytest.mark.parametrize('nelements,twojmax', [(1, 14), (2, 8)],
                         ids=['W-2j14', 'WBe-2j8'])
def test_force_call_builds_no_per_entry_beta_table(nelements, twojmax):
    """The jitted kernel force call at the benchmark's box (N=2000, 26
    slots) with beta a runtime argument: no gather gives a table as long
    as the Y walk's (beta into every CG entry) or as idxz (beta into
    every Z row), per element.  The walk forms each coefficient from beta
    in SMEM."""
    from repro.core.snap import energy_forces
    from repro.kernels.snap_y import Y_HALF_TILE, _y_half_coo_tiles
    if nelements == 1:
        cfg = SnapConfig(twojmax=twojmax, rcut=4.7)
        kw = {}
    else:
        cfg = SnapConfig(twojmax=twojmax, rcutfac=4.8123,
                         radii=(0.5, 0.417932), weights=(1.0, 0.959049))
        kw = dict(species=jnp.asarray(np.arange(2000) % 5 == 0, jnp.int32))
    beta_shape = (cfg.ncoeff,) if nelements == 1 else (2, cfg.ncoeff)
    pair = (2000, 26)
    args = [jax.ShapeDtypeStruct(s, d) for s, d in (
        (beta_shape, jnp.float32), (pair, jnp.float32), (pair, jnp.float32),
        (pair, jnp.float32), (pair, jnp.int32), (pair, jnp.bool_))]
    sizes, kernels = _gather_sizes(jax.jit(
        lambda b, dx, dy, dz, ni, m: energy_forces(
            cfg, b, 0.0, dx, dy, dz, ni, m, impl='kernel', interpret=True,
            **kw)), *args)
    # one table per element on the species path
    table = nelements * _y_half_coo_tiles(twojmax, Y_HALF_TILE)[0].size
    idxz = nelements * build_index(twojmax).y_jjb.size
    assert kernels == 3, kernels
    assert table not in sizes and idxz not in sizes, (table, idxz, sizes)


def test_snap_y_kernel_parity_2j14():
    """Half-plane Y == bs.compute_ylist on the weighted support at 2J=14
    (the mirror fold must hold on the deepest production index space)."""
    cfg = SnapConfig(twojmax=14, rcut=3.0)
    _, disp, _, mask, _ = make_cluster(natoms=4, nnbor=3, seed=7)
    ut = _oracle_ulisttot(cfg, disp, mask)
    rng = np.random.default_rng(7)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff) * 1e-2)
    y_ref = np.asarray(bs.compute_ylist(ut, beta, cfg.index))
    y_k = np.asarray(snap_yi_kernel(cfg, ut, beta, dtype=jnp.float64,
                                    interpret=True, y_tile=16384))
    sup = cfg.index.dedr_weight > 0
    scale = max(1.0, float(np.abs(y_ref).max()))
    np.testing.assert_allclose(y_k[:, sup] / scale, y_ref[:, sup] / scale,
                               atol=1e-10)


def test_kernel_grid_multiblock():
    """natoms > 128 exercises a multi-step grid (block index maps)."""
    cfg = SnapConfig(twojmax=2, rcut=3.0)
    d, *_ = _layout(cfg, 300, 6, seed=0, dtype=jnp.float32)
    assert d.shape[-1] == 384  # 3 lane tiles
    kr, ki = snap_u_pallas(d, twojmax=2, rcut=cfg.rcut, interpret=True)
    rr, ri = ref_snap_u(d, twojmax=2, rcut=cfg.rcut)
    np.testing.assert_allclose(np.asarray(kr), np.asarray(rr),
                               **TOL[jnp.float32])


def test_kernel_isolated_atoms_no_nan():
    """Fully-masked atoms (zero neighbors) must not poison lanes."""
    cfg = SnapConfig(twojmax=4, rcut=3.0)
    natoms, nnbor = 9, 5
    dx = np.zeros((natoms, nnbor))
    mask = np.zeros((natoms, nnbor), bool)
    ut = snap_ui_kernel(cfg, dx, dx, dx, mask, dtype=jnp.float32,
                        interpret=True)
    assert np.isfinite(np.asarray(ut.real)).all()
    # isolated atom: ulisttot == self contribution only
    idx = cfg.index
    expect = np.zeros(idx.idxu_max)
    expect[idx.self_diag] = cfg.wself
    np.testing.assert_allclose(np.asarray(ut[0].real), expect, atol=1e-6)


@pytest.mark.parametrize('twojmax', [2, 4, 8])
@pytest.mark.parametrize('dtype', [jnp.float32, jnp.float64])
def test_fused_de_half_matches_v1(twojmax, dtype):
    """Native half-plane fused-dE kernel (half recursion state AND half Y
    input planes) == full-mirror v1 kernel fed the full-plane expansion
    of the same Y (mirrored/weight-0 rows zero, as in real use)."""
    from repro.kernels.snap_fused_de_half import snap_fused_de_half_pallas
    cfg = SnapConfig(twojmax=twojmax, rcut=3.0)
    idx = cfg.index
    d, *_ = _layout(cfg, 9, 6, seed=twojmax, dtype=dtype)
    rng = np.random.default_rng(twojmax)
    h_shape = (idx.idxu_half_max, d.shape[-1])
    sup = (idx.dedr_weight_half > 0)[:, None]
    yr_h = jnp.asarray(rng.normal(size=h_shape), dtype) * sup
    yi_h = jnp.asarray(rng.normal(size=h_shape), dtype) * sup
    # full-plane expansion: half rows scattered back, mirrored rows zero.
    # NB separate buffers: jnp.asarray of a f64 numpy array is zero-copy
    # on CPU, so reusing one scratch array would alias the first operand.
    full_r = np.zeros((idx.idxu_max, d.shape[-1]))
    full_r[idx.half_to_full] = np.asarray(yr_h)
    yr_f = jnp.asarray(full_r, dtype)
    full_i = np.zeros((idx.idxu_max, d.shape[-1]))
    full_i[idx.half_to_full] = np.asarray(yi_h)
    yi_f = jnp.asarray(full_i, dtype)
    v1 = snap_fused_de_pallas(d, yr_f, yi_f, twojmax=twojmax,
                              rcut=cfg.rcut, interpret=True)
    v2 = snap_fused_de_half_pallas(d, yr_h, yi_h, twojmax=twojmax,
                                   rcut=cfg.rcut, interpret=True)
    np.testing.assert_allclose(np.asarray(v2), np.asarray(v1),
                               **TOL[dtype])
