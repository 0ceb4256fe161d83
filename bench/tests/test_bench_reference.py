"""The plain float64 reference against the program's jnp adjoint, on small
periodic bcc boxes on the CPU: two independent routes to the same
energies and forces."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import inputs  # noqa: E402
import reference  # noqa: E402


def snap(twojmax):
    return dict(twojmax=twojmax, rcut=4.7, rfac0=0.99363, rmin0=0.0,
                switch_flag=True, bzero_flag=True, wself=1.0)


def box_problem(seed, cells=3, sigma=0.05):
    pos, box = inputs.bcc(cells, 3.1652)
    pos = inputs.displaced(pos, box, sigma, inputs.stream(seed, 'pos'))
    return pos, box


@pytest.mark.parametrize('twojmax', [2, 4])
def test_reference_matches_the_program_adjoint(twojmax):
    import jax
    jax.config.update('jax_enable_x64', True)
    from repro.core.snap import SnapConfig, energy_forces
    from repro.md.neighbor import brute_neighbors

    pos, box = box_problem(11)
    s = snap(twojmax)
    cfg = SnapConfig(twojmax=twojmax, rcut=4.7)
    beta = np.random.default_rng(3).normal(size=cfg.ncoeff) * 5e-3
    ni, m, disp, _ = brute_neighbors(pos, box, 4.7, 32)
    _, e_atom, f = energy_forces(cfg, beta, 0.1, disp[..., 0], disp[..., 1],
                                 disp[..., 2], ni, m, impl='adjoint')
    atoms = np.array([0, 7, 30])
    e_ref, f_ref = reference.forces_on(s, beta, 0.1, pos, box, atoms)
    np.testing.assert_allclose(e_ref, np.asarray(e_atom)[atoms], rtol=1e-12)
    np.testing.assert_allclose(f_ref, np.asarray(f)[atoms], atol=1e-12)
    e_tot, f_all = reference.energy_and_forces(s, beta, 0.1, pos, box)
    assert e_tot == pytest.approx(float(np.sum(e_atom)), rel=1e-12)
    np.testing.assert_allclose(f_all, np.asarray(f), atol=1e-12)


def test_local_verlet_matches_whole_box_verlet():
    """On a box small enough that the neighbourhood is every atom, the
    local integration is the whole-box velocity Verlet."""
    import jax
    jax.config.update('jax_enable_x64', True)
    pos, box = box_problem(5)
    s = snap(2)
    beta = np.random.default_rng(4).normal(size=5) * 5e-3
    vel = inputs.velocities(len(pos), 300.0, 183.84, inputs.stream(5, 'v'))
    dt, acc = 0.0005, inputs.ACC_CONV / 183.84
    x, v = pos.copy(), vel.copy()
    _, f = reference.energy_and_forces(s, beta, 0.0, x, box)
    for _ in range(3):
        v += 0.5 * dt * acc * f
        x += dt * v
        _, f = reference.energy_and_forces(s, beta, 0.0, x, box)
        v += 0.5 * dt * acc * f
    atoms = np.array([2, 9])
    got = reference.verlet_local(s, beta, 0.0, pos, vel, box, atoms, 3, dt,
                                 acc)
    np.testing.assert_allclose(got, x[atoms], atol=1e-12)
