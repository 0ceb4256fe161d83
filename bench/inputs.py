"""Inputs made from ``--seed``: bcc boxes, displacements, velocities and
the model's coefficients.  The same seed gives the same inputs; each use
draws from its own stream (``stream``), so changing one never shifts
another."""

from __future__ import annotations

import numpy as np

KB = 8.617333262e-5          # eV/K
ACC_CONV = 9648.533212331    # (eV/Å) / (g/mol) in Å/ps^2


def stream(seed: int, *tag) -> np.random.Generator:
    """An independent generator for ``tag`` under ``seed``."""
    return np.random.default_rng([int(seed) % 2 ** 63, *map(_num, tag)])


def _num(t) -> int:
    if isinstance(t, int):
        return t
    return int.from_bytes(str(t).encode()[:8].ljust(8, b'\0'), 'little')


def bcc(cells: int, a: float):
    """(positions [2 cells^3, 3], box [3]) of a cubic bcc supercell."""
    g = np.stack(np.meshgrid(*[np.arange(cells)] * 3, indexing='ij'),
                 -1).reshape(-1, 3).astype(np.float64)
    pos = np.concatenate([g, g + 0.5]) * a
    return pos, np.full(3, cells * a)


def lattice(config: dict):
    """The configuration's bcc box of ``natoms`` atoms (2 cells^3)."""
    n = int(config['natoms'])
    cells = round((n / 2) ** (1 / 3))
    if 2 * cells ** 3 != n:
        raise ValueError(f'natoms {n} is not a cubic bcc box (2 cells^3)')
    return bcc(cells, float(config['lattice_a']))


def displaced(pos, box, sigma: float, rng):
    """Gaussian displacement of every site, wrapped into the box."""
    return np.mod(pos + rng.normal(scale=sigma, size=pos.shape), box)


def velocities(n: int, temp: float, mass: float, rng):
    """Maxwell-Boltzmann velocities (Å/ps) with zero total momentum."""
    v = rng.normal(scale=np.sqrt(KB * temp / (mass / ACC_CONV)), size=(n, 3))
    return v - v.mean(0)


def beta(config: dict, rng):
    """Random linear SNAP coefficients (random weights)."""
    return rng.normal(size=int(config['ncoeff'])) * float(
        config['beta_scale'])


def snap_config(config: dict):
    """The program's SnapConfig for the configuration file."""
    from repro.core.snap import SnapConfig
    s = config['snap']
    return SnapConfig(twojmax=int(s['twojmax']), rcut=float(s['rcut']),
                      rfac0=float(s['rfac0']), rmin0=float(s['rmin0']),
                      switch_flag=bool(s['switch_flag']),
                      bzero_flag=bool(s['bzero_flag']),
                      wself=float(s['wself']))
