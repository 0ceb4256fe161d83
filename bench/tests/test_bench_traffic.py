"""Inputs are made from the seed alone: the same seed gives the same
inputs and the same sampled atoms, another seed others."""

import copy
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import compare  # noqa: E402
import harness  # noqa: E402
import inputs  # noqa: E402

BIG = 2 ** 31 + 12345        # seeds above 32 signed bits


def test_streams_repeat_and_differ():
    a = inputs.stream(BIG, 'displacement').normal(size=5)
    b = inputs.stream(BIG, 'displacement').normal(size=5)
    c = inputs.stream(BIG, 'velocity').normal(size=5)
    d = inputs.stream(BIG + 1, 'displacement').normal(size=5)
    np.testing.assert_array_equal(a, b)
    assert not np.allclose(a, c) and not np.allclose(a, d)
    assert inputs.stream(-3, 'x').normal() == inputs.stream(-3, 'x').normal()


def test_md_inputs_repeat_from_the_seed():
    pos, box = inputs.bcc(4, 3.1652)
    p1 = inputs.displaced(pos, box, 0.03, inputs.stream(BIG, 'displacement'))
    p2 = inputs.displaced(pos, box, 0.03, inputs.stream(BIG, 'displacement'))
    np.testing.assert_array_equal(p1, p2)
    assert (p1 >= 0).all() and (p1 < box).all()
    v = inputs.velocities(len(pos), 300.0, 183.84, inputs.stream(BIG, 'v'))
    np.testing.assert_allclose(v.mean(0), 0.0, atol=1e-12)


@pytest.mark.parametrize('cell', ['force_2j14_bcc2k', 'md_2j8_bcc16k'])
def test_sample_holds_an_atom_of_every_lane_tile(cell):
    """One atom drawn from the seed in every 128 consecutive atoms (a
    kernel's lane tile), the same under one seed, others under another."""
    files = copy.deepcopy(harness.cell_files(cell))
    run = harness.Run(name=cell, seed=BIG, seconds=10.0, trace=False,
                      **files)
    n = int(files['config']['natoms'])
    atoms = compare.sampled_atoms(run, n)
    assert files['traffic']['sample_block'] == 128
    np.testing.assert_array_equal(np.unique(atoms // 128),
                                  np.arange(-(-n // 128)))
    assert atoms.max() < n
    np.testing.assert_array_equal(atoms, compare.sampled_atoms(run, n))
    run.seed = BIG + 1
    assert not np.array_equal(atoms, compare.sampled_atoms(run, n))
    pos, box = inputs.lattice(files['config'])
    assert len(pos) == n and (box == box[0]).all()
