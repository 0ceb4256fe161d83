"""The comparisons that decide ``correct``: what a timed path produced
against the float64 reference, each number with the cell's limit
(``limits`` in ``workloads/<cell>.json``)."""

from __future__ import annotations

import numpy as np

import inputs
import reference


def rel_err(x, ref) -> float:
    """Largest absolute deviation over the largest reference magnitude."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.abs(x - ref).max() / np.abs(ref).max())


def sampled_atoms(run, natoms: int) -> np.ndarray:
    """One atom drawn from the seed in every block of ``sample_block``
    consecutive atoms.  The kernels put 128 consecutive atoms on the
    lanes of one tile, so at a block of 128 every tile has an atom in the
    sample."""
    rng = inputs.stream(run.seed, 'sample')
    block = int(run.traffic['sample_block'])
    starts = np.arange(0, natoms, block)
    width = np.minimum(block, natoms - starts)
    return starts + (rng.random(len(starts)) * width).astype(np.int64)


def atoms_against_reference(run, pos, box, beta, forces, e_atom=None):
    """[(name, value, limit)] for the sampled atoms of one configuration:
    their forces, and, where the timed path returns per-atom energies,
    the energies of every atom whose energy the reference computes for
    those forces (the sampled atoms and their neighbours)."""
    atoms = sampled_atoms(run, len(pos))
    _, f_ref, centres, e_ref = reference.forces_on(
        run.snap, beta, 0.0, pos, box, atoms, centre_energies=True)
    lim = run.workload['limits']
    out = [('force_rel_err', rel_err(np.asarray(forces)[atoms], f_ref),
            lim['force_rel_err'])]
    if e_atom is not None:
        out.append(('energy_rel_err', rel_err(np.asarray(e_atom)[centres],
                                              e_ref), lim['energy_rel_err']))
    return out
