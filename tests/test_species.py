"""Multi-element (species path) SNAP: the element table of ``SnapConfig``,
the three species kernels in interpret mode against the jnp oracles, the
kernel force pipeline against the jnp adjoint and reverse-mode autodiff,
and the device-loop MD with per-atom species and masses.

The box is a two-element bcc alloy at the W-Be table of Wood et al.
(PRB 99, 184305, 2019: rcutfac 4.8123, R 0.5 and 0.417932, w 1.0 and
0.959049), so the three pair types have different cutoffs and the lists,
built at the largest, hold pairs that only the per-pair cut removes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bispectrum as bs
from repro.core.snap import (SnapConfig, energy_forces,
                             energy_forces_autodiff, species_pairs)
from repro.kernels.ops import _species_layout, half_planes_to_full
from repro.kernels.ref import ref_snap_fused_de, ref_snap_u
from repro.kernels.snap_fused_de_half import snap_de_species_pallas
from repro.kernels.snap_u import snap_u_species_pallas
from repro.kernels.snap_y import snap_y_species_pallas
from repro.md.integrate import (MDState, init_velocities, run_nve,
                                species_pair_counts, temperature)
from repro.md.lattice import bcc_alloy, perturb, random_species
from repro.md.neighbor import brute_neighbors

from test_sharded_forces import run_py

WBE = dict(rcutfac=4.8123, radii=(0.5, 0.417932), weights=(1.0, 0.959049))
MASSES = np.array([183.84, 9.012182])


def wbe(twojmax=4):
    return SnapConfig(twojmax=twojmax, **WBE)


def alloy(cells=(5, 5, 4), fraction=0.2, seed=1):
    """A perturbed W-Be bcc alloy with lists at the largest cutoff."""
    pos, box, sp = bcc_alloy(*cells, a=3.1652, fraction=fraction, seed=seed)
    pos = perturb(pos, 0.05, seed=seed + 1)
    nbr, mask, disp, shifts = brute_neighbors(pos, box, wbe().rcut,
                                              max_nbors=40)
    return pos, box, sp, nbr, mask, disp, shifts


def coefficients(cfg, seed=0):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(cfg.nelements, cfg.ncoeff)) * 5e-2),
            jnp.asarray([0.3, -0.4]))


def split(disp):
    return (jnp.asarray(disp[..., 0]), jnp.asarray(disp[..., 1]),
            jnp.asarray(disp[..., 2]))


def test_element_table_derives_the_cutoffs():
    cfg = wbe()
    assert cfg.species_path and cfg.nelements == 2
    np.testing.assert_allclose(
        cfg.pair_rcut, [[4.8123, 4.417364], [4.417364, 4.022428]], atol=1e-6)
    assert cfg.rcut == cfg.pair_rcut.max()
    with pytest.raises(ValueError):
        SnapConfig(rcutfac=4.8, radii=(0.5, 0.4), weights=(1.0,))
    with pytest.raises(ValueError):
        SnapConfig(weights=(1.0,))
    with pytest.raises(ValueError):
        SnapConfig(rcutfac=4.8, radii=(0.5,), weights=(0.9,))


def _pair_counts(cfg, fraction):
    pos, box, sp = bcc_alloy(4, 4, 4, 3.1652, fraction, seed=0)
    nbr, mask, _, shifts = brute_neighbors(pos, box, cfg.rcut, max_nbors=40)
    counts = np.asarray(species_pair_counts(
        cfg, sp, jnp.asarray(pos), jnp.asarray(nbr), jnp.asarray(shifts),
        jnp.asarray(mask)))
    return counts, sp, pos, nbr, mask, shifts


@pytest.mark.parametrize('fraction,element,per_atom', [
    (0.0, 0, 26),     # W-W cut 4.8123: the 2.741, 3.165 and 4.476 shells
    (1.0, 1, 14),     # Be-Be cut 4.0224: the first two shells only
])
def test_bcc_pure_neighbour_counts(fraction, element, per_atom):
    counts, sp, *_ = _pair_counts(wbe(), fraction)
    want = np.zeros((2, 2), int)
    want[element, element] = per_atom * len(sp)
    np.testing.assert_array_equal(counts, want)


def test_bcc_alloy_neighbour_counts():
    """A W-Be pair is cut at 4.4174, so the 4.476 Å shell drops out of it:
    a W site counts its 14 nearest sites and the W sites of the third
    shell; a Be site its 14 nearest sites."""
    cfg = wbe()
    counts, sp, pos, nbr, mask, shifts = _pair_counts(cfg, 0.5)
    r = np.linalg.norm(pos[nbr] + shifts - pos[:, None], axis=-1)
    near = mask & (r < 3.5)
    third_w = mask & (r > 4.0) & (sp[nbr] == 0)
    per_site = np.where(sp == 0, near.sum(1) + third_w.sum(1),
                        near.sum(1))
    assert (near.sum(1) == 14).all()
    assert counts.sum() == per_site.sum()
    assert counts[0, 1] == counts[1, 0] == (near & (sp[:, None] == 1)
                                            & (sp[nbr] == 0)).sum()


def test_single_element_table_is_the_scalar_config():
    """One element of weight 1 runs the single-element path at
    rcut = rcutfac * 2R: the same config, bit for bit."""
    table = SnapConfig(twojmax=4, rcutfac=2.0, radii=(1.0,), weights=(1.0,))
    scalar = SnapConfig(twojmax=4, rcut=4.0)
    assert not table.species_path and table.rcut == scalar.rcut
    pos, box, sp, nbr, mask, disp, _ = alloy(cells=(3, 3, 3))
    beta = coefficients(table)[0][0]
    for impl, kw in (('adjoint', {}),
                     ('kernel', dict(interpret=True, dtype=jnp.float64))):
        a = energy_forces(table, beta, 0.1, *split(disp), nbr, mask,
                          impl=impl, species=sp, **kw)
        b = energy_forces(scalar, beta, 0.1, *split(disp), nbr, mask,
                          impl=impl, **kw)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize('impl', ['adjoint', 'kernel'])
def test_identical_elements_reproduce_one_element(impl):
    """Two elements with the same radius, weight and coefficients give
    the single-element energies and forces (f64, to round-off: the species
    path reaches the same sums by another route)."""
    same = SnapConfig(twojmax=4, rcutfac=4.8123, radii=(0.5, 0.5),
                      weights=(1.0, 1.0))
    one = SnapConfig(twojmax=4, rcut=4.8123)
    assert same.species_path
    pos, box, sp, nbr, mask, disp, _ = alloy(cells=(3, 3, 3))
    beta = coefficients(one)[0][0]
    kw = dict(interpret=True, dtype=jnp.float64) if impl == 'kernel' else {}
    e2, ea2, f2 = energy_forces(same, jnp.stack([beta, beta]), 0.1,
                                *split(disp), nbr, mask, impl=impl,
                                species=sp, **kw)
    e1, ea1, f1 = energy_forces(one, beta, 0.1, *split(disp), nbr, mask,
                                impl=impl, **kw)
    np.testing.assert_allclose(np.asarray(ea2), np.asarray(ea1), rtol=1e-12,
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1),
                               atol=1e-12 * float(jnp.abs(f1).max()))


def test_species_path_needs_species():
    pos, box, sp, nbr, mask, disp, _ = alloy(cells=(3, 3, 3))
    cfg = wbe()
    beta, beta0 = coefficients(cfg)
    with pytest.raises(ValueError, match='species'):
        energy_forces(cfg, beta, beta0, *split(disp), nbr, mask)
    with pytest.raises(ValueError, match='single-element'):
        energy_forces(cfg, beta, beta0, *split(disp), nbr, mask,
                      impl='baseline', species=sp)
    with pytest.raises(ValueError, match='beta of shape'):
        energy_forces(cfg, beta[0], beta0, *split(disp), nbr, mask,
                      species=sp)


@pytest.fixture(scope='module')
def stage_inputs():
    """The species per-pair array of a 200-atom alloy (two lane tiles)."""
    cfg = wbe()
    pos, box, sp, nbr, mask, disp, _ = alloy()
    sp_i, w_j, rc = species_pairs(cfg, sp, jnp.asarray(nbr))
    d, ok, n = _species_layout(cfg, *split(disp), jnp.asarray(mask), w_j, rc,
                               jnp.float64)
    assert d.shape == (nbr.shape[1], 5, 256)
    # some slots of the lists lie beyond their own pair's cutoff
    r = np.linalg.norm(disp, axis=-1)
    assert (mask & (r >= np.asarray(rc))).any()
    assert int(ok.sum()) == int((mask & (r < np.asarray(rc))).sum())
    return cfg, d, sp_i


def test_u_species_kernel_matches_oracle(stage_inputs):
    """f64 interpret mode against the jnp U (same equations, another
    route): round-off only."""
    cfg, d, _ = stage_inputs
    kr, ki = snap_u_species_pallas(d, twojmax=cfg.twojmax, interpret=True)
    ur, ui = half_planes_to_full(cfg, kr, ki)
    rr, ri = ref_snap_u(d, twojmax=cfg.twojmax)
    np.testing.assert_allclose(np.asarray(ur), np.asarray(rr), atol=1e-12)
    np.testing.assert_allclose(np.asarray(ui), np.asarray(ri), atol=1e-12)


def test_de_species_kernel_matches_oracle(stage_inputs):
    cfg, d, _ = stage_inputs
    idx = cfg.index
    rng = np.random.default_rng(4)
    y = rng.normal(size=(2, idx.idxu_max, d.shape[-1]))
    y[:, idx.dedr_weight == 0] = 0.0       # the kernel reads half rows only
    half = np.asarray(idx.half_to_full)
    out = snap_de_species_pallas(d, jnp.asarray(y[0, half]),
                                 jnp.asarray(y[1, half]),
                                 twojmax=cfg.twojmax, interpret=True)
    ref = ref_snap_fused_de(d, jnp.asarray(y[0]), jnp.asarray(y[1]),
                            twojmax=cfg.twojmax)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-11)


def test_y_species_kernel_matches_per_atom_beta(stage_inputs):
    """Each lane's Y uses its own element's coefficients: the walk against
    ``compute_ylist`` with one beta row per atom."""
    cfg, d, sp_i = stage_inputs
    idx = cfg.index
    beta, _ = coefficients(cfg, seed=2)
    kr, ki = snap_u_species_pallas(d, twojmax=cfg.twojmax, interpret=True)
    kr = kr.at[np.asarray(idx.self_diag_half)].add(cfg.wself)
    sp_lanes = jnp.pad(sp_i, (0, d.shape[-1] - len(sp_i)))
    yr, yi = snap_y_species_pallas(kr, ki, beta, sp_lanes,
                                   twojmax=cfg.twojmax, interpret=True)
    ur, ui = half_planes_to_full(cfg, kr, ki)
    y_ref = bs.compute_ylist((ur + 1j * ui).T, beta[sp_lanes], idx)
    got = np.zeros_like(np.asarray(y_ref))
    got[:, idx.half_to_full] = np.asarray((yr + 1j * yi).T)
    w = idx.dedr_weight > 0
    np.testing.assert_allclose(got[:, w], np.asarray(y_ref)[:, w],
                               atol=1e-12 * float(np.abs(y_ref).max()))


@pytest.mark.parametrize('twojmax', [4, 8])
def test_y_species_walk_forms_each_elements_coefficients(twojmax):
    """The species walk, beta [2, ncoeff] in SMEM, against
    ``compute_ylist`` with each lane's own beta row: random U planes (the
    half rows, mirrored to the full ones), 200 atoms, so the second lane
    tile is partial, and a lane element pattern with both elements in
    every tile."""
    cfg = wbe(twojmax)
    idx = cfg.index
    natoms, lanes = 200, 256
    rng = np.random.default_rng(twojmax)
    kr, ki = (jnp.asarray(rng.normal(size=(idx.idxu_half_max, lanes)))
              for _ in range(2))
    sp_lanes = jnp.asarray(rng.integers(0, 2, size=lanes), jnp.int32)
    beta, _ = coefficients(cfg, seed=twojmax)
    yr, yi = snap_y_species_pallas(kr, ki, beta, sp_lanes,
                                   twojmax=twojmax, interpret=True)
    ur, ui = half_planes_to_full(cfg, kr, ki)
    u = (ur + 1j * ui).T[:natoms]
    y_ref = np.asarray(bs.compute_ylist(u, beta[sp_lanes[:natoms]], idx))
    got = np.zeros_like(y_ref)
    got[:, idx.half_to_full] = np.asarray((yr + 1j * yi).T)[:natoms]
    w = idx.dedr_weight > 0
    np.testing.assert_allclose(got[:, w], y_ref[:, w],
                               atol=1e-12 * float(np.abs(y_ref).max()))


@pytest.mark.parametrize('dtype,tol', [
    # f64 kernels: the same sums as the adjoint in another order
    (jnp.float64, 1e-10),
    # f32 kernels: the force is a cancelling sum of ~20 pair terms, each
    # good to ~1e-7 relative; 5e-5 of the largest force, as the
    # single-element pipeline tests allow
    (jnp.float32, 5e-5),
])
def test_species_pipeline_matches_adjoint(dtype, tol):
    """2J=4, two elements, 200 atoms, seeded coefficients per element: the
    species kernel path against the jnp adjoint with species, energies
    (total and per atom) and forces."""
    cfg = wbe()
    pos, box, sp, nbr, mask, disp, _ = alloy()
    beta, beta0 = coefficients(cfg)
    e_a, ea_a, f_a = energy_forces(cfg, beta, beta0, *split(disp), nbr,
                                   mask, impl='adjoint', species=sp)
    e_k, ea_k, f_k = energy_forces(cfg, beta, beta0, *split(disp), nbr,
                                   mask, impl='kernel', species=sp,
                                   dtype=dtype, interpret=True)
    fscale = float(jnp.abs(f_a).max())
    np.testing.assert_allclose(np.asarray(f_k), np.asarray(f_a),
                               atol=tol * fscale)
    escale = float(jnp.abs(ea_a).max())
    np.testing.assert_allclose(np.asarray(ea_k), np.asarray(ea_a),
                               atol=tol * escale)
    np.testing.assert_allclose(float(e_k), float(e_a),
                               atol=tol * escale * len(pos) ** 0.5)


def test_adjoint_species_matches_autodiff():
    """The jnp adjoint with species against reverse-mode autodiff of the
    species energy: forces are -dE/dr of the energy they come with."""
    cfg = wbe()
    pos, box, sp, nbr, mask, disp, shifts = alloy(cells=(3, 3, 3))
    beta, beta0 = coefficients(cfg, seed=3)
    e_g, f_g = energy_forces_autodiff(cfg, beta, beta0, jnp.asarray(pos),
                                      nbr, shifts, mask, species=sp)
    e_a, _, f_a = energy_forces(cfg, beta, beta0, *split(disp), nbr, mask,
                                species=sp)
    np.testing.assert_allclose(float(e_a), float(e_g), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(f_a), np.asarray(f_g),
                               atol=1e-11 * float(jnp.abs(f_g).max()))


def test_per_atom_masses():
    """Velocities by species mass: zero momentum, the set temperature in
    expectation, and the light element faster."""
    sp = random_species(4000, 0.2, seed=5)
    m = MASSES[sp]
    v = init_velocities(len(sp), 300.0, mass=m, seed=6)
    np.testing.assert_allclose((m[:, None] * v).sum(0), 0.0, atol=1e-9)
    T, _ = temperature(v, m)
    assert abs(T - 300.0) < 15.0
    speed = np.linalg.norm(v, axis=1)
    ratio = speed[sp == 1].mean() / speed[sp == 0].mean()
    assert 4.0 < ratio < 5.0          # sqrt(183.84 / 9.012) = 4.52


def test_device_loop_two_species_kernel_matches_adjoint():
    """run_nve(loop='device') with species and per-atom masses: the kernel
    path tracks the adjoint's trajectory, one trace, and records the pairs
    inside their cutoff by element pair."""
    cfg = SnapConfig(twojmax=2, **WBE)
    pos, box, sp = bcc_alloy(3, 3, 3, 3.1652, 0.3, seed=1)
    pos = perturb(pos, 0.03, seed=2)
    mass = MASSES[sp]
    beta = np.random.default_rng(3).normal(size=(2, cfg.ncoeff)) * 5e-3
    outs, pairs = {}, {}
    for impl, kw in (('kernel', dict(interpret=True, dtype=jnp.float64)),
                     ('adjoint', {})):
        state = MDState(pos=pos.copy(), box=box,
                        vel=init_velocities(len(pos), 300.0, mass=mass,
                                            seed=4))
        cache = {}
        _, thermo = run_nve(cfg, beta, 0.0, state, n_steps=4, dt=0.0005,
                            mass=mass, log_every=2, loop='device', skin=0.6,
                            impl=impl, force_kwargs=kw, fn_cache=cache,
                            species=sp)
        assert cache['device_trace_count']['traces'] == 1
        outs[impl] = np.array([[t['T'], t['pe'], t['etot']]
                               for t in thermo])
        pairs[impl] = cache['species_pairs']
    np.testing.assert_allclose(outs['kernel'], outs['adjoint'], rtol=1e-10)
    assert pairs['kernel'] == pairs['adjoint']
    counts = np.array(pairs['kernel'])
    assert counts[0, 1] == counts[1, 0] > 0 and counts.sum() > 0
    drift = np.ptp(outs['kernel'][:, 2])
    assert drift < 1e-6 * abs(outs['kernel'][0, 2]), drift
    with pytest.raises(ValueError, match='species'):
        run_nve(cfg, beta, 0.0, state, n_steps=2, mass=mass, loop='device')


def test_species_pipeline_atom_sharded():
    """The species kernel path under an atom-sharded shard_map on two
    host devices: every shard takes its own block of centre elements."""
    run_py('''
        import jax
        jax.config.update('jax_enable_x64', True)
        import numpy as np, jax.numpy as jnp
        from repro.core.snap import SnapConfig, energy_forces
        from repro.kernels.ops import make_sharded_force_fn
        from repro.launch.sharding import make_atom_mesh
        from repro.md.lattice import bcc_alloy, perturb
        from repro.md.neighbor import brute_neighbors

        assert len(jax.devices()) == 2
        cfg = SnapConfig(twojmax=2, rcutfac=4.8123,
                         radii=(0.5, 0.417932), weights=(1.0, 0.959049))
        pos, box, sp = bcc_alloy(3, 3, 3, 3.1652, 0.4, seed=1)
        pos = perturb(pos, 0.05, seed=2)
        nbr, mask, disp, _ = brute_neighbors(pos, box, cfg.rcut, 40)
        rng = np.random.default_rng(0)
        beta = jnp.asarray(rng.normal(size=(2, cfg.ncoeff)) * 5e-2)
        args = (jnp.asarray(disp[..., 0]), jnp.asarray(disp[..., 1]),
                jnp.asarray(disp[..., 2]), jnp.asarray(nbr),
                jnp.asarray(mask))
        kw = dict(dtype=jnp.float64, interpret=True, species=sp)
        e0, ea0, f0 = energy_forces(cfg, beta, 0.1, *args, impl='kernel',
                                    **kw)
        e1, ea1, f1 = make_sharded_force_fn(
            cfg, beta, 0.1, make_atom_mesh(2), impl='kernel', **kw)(*args)
        np.testing.assert_allclose(float(e1), float(e0), rtol=1e-12)
        np.testing.assert_allclose(np.asarray(ea1), np.asarray(ea0),
                                   atol=1e-12)
        np.testing.assert_allclose(np.asarray(f1), np.asarray(f0),
                                   atol=1e-12 * float(jnp.abs(f0).max()))
        print('ok')
    ''')
