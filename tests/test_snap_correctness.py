"""Cross-validation of the three SNAP force pipelines + known invariants.

The paper's central claim (Sec. IV) is that the adjoint refactorization is
*exactly* equivalent to the original Z/dB formulation — and equivalent to
reverse-mode differentiation.  These tests enforce all three equalities to
fp64 round-off.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bispectrum as bs
from repro.core.indices import build_index, clebsch_gordan_block
from repro.core.snap import (SnapConfig, _pair_geometry, compute_bispectrum,
                             energy_forces_adjoint, energy_forces_autodiff,
                             energy_forces_baseline, energy_from_ylist)
from repro.core.ulist import compute_dulist, compute_ulist, compute_ulisttot

from conftest import make_cluster


@pytest.mark.parametrize('twojmax', [2, 4, 6, 8])
def test_pipelines_agree(twojmax):
    cfg = SnapConfig(twojmax=twojmax, rcut=3.0)
    pos, disp, nbr_idx, mask, shifts = make_cluster(seed=twojmax)
    rng = np.random.default_rng(1)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    dx, dy, dz = disp[..., 0], disp[..., 1], disp[..., 2]

    e_a, ea, f_a = energy_forces_adjoint(cfg, beta, 0.3, dx, dy, dz,
                                         nbr_idx, mask)
    e_b, eb, f_b = energy_forces_baseline(cfg, beta, 0.3, dx, dy, dz,
                                          nbr_idx, mask)
    e_g, f_g = energy_forces_autodiff(cfg, beta, 0.3, jnp.asarray(pos),
                                      nbr_idx, shifts, mask)
    np.testing.assert_allclose(e_a, e_g, rtol=1e-12)
    np.testing.assert_allclose(e_b, e_g, rtol=1e-12)
    scale = np.abs(f_g).max()
    np.testing.assert_allclose(f_a, f_g, atol=1e-11 * scale)
    np.testing.assert_allclose(f_b, f_g, atol=1e-11 * scale)


@pytest.mark.parametrize('atom_chunks', [2, 4])
@pytest.mark.parametrize('energy_via_z', [False, True])
def test_adjoint_atom_chunks_match_one_block(cfg_2j4, atom_chunks,
                                             energy_via_z):
    """Running the per-atom stages block by block changes nothing but the
    memory held at once: forces, per-atom and total energy match."""
    _, disp, nbr_idx, mask, _ = make_cluster(seed=5)
    beta = jnp.asarray(np.random.default_rng(4).normal(
        size=cfg_2j4.ncoeff))
    args = (cfg_2j4, beta, 0.3, disp[..., 0], disp[..., 1], disp[..., 2],
            nbr_idx, mask)
    e1, ea1, f1 = energy_forces_adjoint(*args, energy_via_z=energy_via_z)
    ek, eak, fk = energy_forces_adjoint(*args, energy_via_z=energy_via_z,
                                        atom_chunks=atom_chunks)
    np.testing.assert_allclose(ek, e1, rtol=1e-13)
    np.testing.assert_allclose(eak, ea1, rtol=1e-13, atol=1e-13)
    np.testing.assert_allclose(fk, f1, rtol=1e-12, atol=1e-13)


def test_adjoint_atom_chunks_must_divide_natoms(cfg_2j4):
    _, disp, nbr_idx, mask, _ = make_cluster(seed=5)
    beta = jnp.zeros(cfg_2j4.ncoeff)
    with pytest.raises(ValueError, match='must divide'):
        energy_forces_adjoint(cfg_2j4, beta, 0.0, disp[..., 0], disp[..., 1],
                              disp[..., 2], nbr_idx, mask, atom_chunks=3)


def test_energy_from_y_matches_z_path(cfg_2j8):
    """The (2/3) U*.Y energy identity vs the canonical Z->B path."""
    cfg = cfg_2j8
    _, disp, nbr_idx, mask, _ = make_cluster(seed=3)
    rng = np.random.default_rng(2)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    dx, dy, dz = disp[..., 0], disp[..., 1], disp[..., 2]
    idx = cfg.index
    geom, _, ok = _pair_geometry(cfg, jnp.asarray(dx), jnp.asarray(dy),
                                 jnp.asarray(dz), jnp.asarray(mask),
                                 grad=False)
    u = compute_ulist(geom, idx, cfg.dtype)
    ut = compute_ulisttot(u, geom.sfac, ok, idx, cfg.wself)
    y = bs.compute_ylist(ut, beta, idx)
    e_y = energy_from_ylist(cfg, ut, y, beta, 0.0)
    z = bs.compute_zlist(ut, idx)
    b = bs.compute_blist(ut, z, idx, cfg.bzero_flag)
    e_z = b @ beta
    np.testing.assert_allclose(e_y, e_z, rtol=1e-11, atol=1e-11)


def test_isolated_atom_bzero(cfg_2j8):
    """With bzero subtraction, an atom with no neighbors has B == 0."""
    K = 4
    b = compute_bispectrum(cfg_2j8, np.zeros((1, K)), np.zeros((1, K)),
                           np.zeros((1, K)), np.zeros((1, K), bool))
    np.testing.assert_allclose(np.asarray(b), 0.0, atol=1e-12)


def test_dulist_matches_jvp(cfg_2j4):
    """Hand-rolled dual recursion == forward-mode AD of sfac*U."""
    import jax
    cfg = cfg_2j4
    idx = cfg.index
    rng = np.random.default_rng(5)
    d = rng.uniform(-1.5, 1.5, (16, 3))
    d = d[np.linalg.norm(d, axis=1) < 0.9 * cfg.rcut][:8]
    dx, dy, dz = (jnp.asarray(d[:, i]) for i in range(3))
    mask = jnp.ones(d.shape[0], bool)
    geom, dgeom, ok = _pair_geometry(cfg, dx, dy, dz, mask, grad=True)
    u, du = compute_dulist(geom, dgeom, idx, cfg.dtype)

    def sfac_u(vec):
        g, _, _ = _pair_geometry(cfg, vec[..., 0], vec[..., 1], vec[..., 2],
                                 mask, grad=False)
        return compute_ulist(g, idx, cfg.dtype) * g.sfac[..., None]

    for k in range(3):
        tang = jnp.zeros_like(jnp.asarray(d)).at[:, k].set(1.0)
        _, du_jvp = jax.jvp(sfac_u, (jnp.asarray(d),), (tang,))
        np.testing.assert_allclose(np.asarray(du[:, k, :]),
                                   np.asarray(du_jvp), atol=1e-12)


def test_cg_known_values():
    """Spot-check Clebsch-Gordan values against analytic results.

    With doubled indices, block (j1=1, j2=1, j=2) couples two spin-1/2's into
    spin-1: <1/2 1/2|1 1> = 1, <1/2 -1/2|1 0> = 1/sqrt(2).
    """
    cg = clebsch_gordan_block(1, 1, 2)
    np.testing.assert_allclose(cg[1, 1], 1.0, rtol=1e-14)       # up,up -> m=1
    np.testing.assert_allclose(cg[1, 0], 1 / np.sqrt(2), rtol=1e-14)
    np.testing.assert_allclose(cg[0, 1], 1 / np.sqrt(2), rtol=1e-14)
    # singlet coupling (j=0): <1/2 -1/2|0 0> = +-1/sqrt(2) antisymmetric
    cg0 = clebsch_gordan_block(1, 1, 0)
    np.testing.assert_allclose(abs(cg0[0, 1]), 1 / np.sqrt(2), rtol=1e-14)
    np.testing.assert_allclose(cg0[0, 1], -cg0[1, 0], rtol=1e-14)


def test_u_unitarity(cfg_2j8):
    """Each raw Wigner layer U_j is unitary: sum_ma |u(mb,ma)|^2 == 1."""
    cfg = cfg_2j8
    idx = cfg.index
    d = np.array([[0.7, -0.4, 1.1]])
    geom, _, _ = _pair_geometry(cfg, d[:, 0], d[:, 1], d[:, 2],
                                np.ones(1, bool), grad=False)
    u = np.asarray(compute_ulist(geom, idx, cfg.dtype))[0]
    for j in range(cfg.twojmax + 1):
        blk = u[idx.idxu_block[j]: idx.idxu_block[j] + (j + 1) ** 2]
        m = blk.reshape(j + 1, j + 1)
        np.testing.assert_allclose(m @ m.conj().T, np.eye(j + 1), atol=1e-12)


def test_force_sum_zero(cfg_2j8):
    """Translation invariance => total force is zero (Newton's 3rd law)."""
    cfg = cfg_2j8
    _, disp, nbr_idx, mask, _ = make_cluster(seed=7)
    # symmetric neighbor lists required: make_cluster builds both directions
    rng = np.random.default_rng(3)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff))
    dx, dy, dz = disp[..., 0], disp[..., 1], disp[..., 2]
    _, _, f = energy_forces_adjoint(cfg, beta, 0.0, dx, dy, dz, nbr_idx,
                                    mask)
    np.testing.assert_allclose(np.asarray(f).sum(0), 0.0, atol=1e-10)
