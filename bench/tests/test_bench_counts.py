"""bench/counts.py and bench/snapidx.py: hand counts at 2J=2, and the
program's own index sizes at 2J=8 and 2J=14."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import counts  # noqa: E402
import snapidx  # noqa: E402


def test_index_sets_by_hand_at_2j2():
    assert snapidx.u_size(2) == 1 + 4 + 9
    assert snapidx.u_half_size(2) == 1 + 2 + 6
    assert snapidx.triples(2) == [(0, 0, 0), (1, 0, 1), (1, 1, 0), (1, 1, 2),
                                  (2, 0, 2), (2, 1, 1), (2, 2, 0), (2, 2, 2)]
    assert snapidx.bispectrum_triples(2) == [(0, 0, 0), (1, 0, 1), (1, 1, 2),
                                             (2, 0, 2), (2, 2, 2)]


def test_stage_counts_by_hand_at_2j2():
    # rows 2mb <= j of layers 1 and 2: 1*2 + 2*3 = 8 elements
    u = counts.u_stage(2, natoms=1, npairs=1)
    assert u.flops == 18 * 8 + 4 * 9
    assert u.bytes == 16 + 2 * 9 * 4
    de = counts.de_stage(2, natoms=1, npairs=1)
    assert de.flops == 150 * 8
    assert de.bytes == 16 + 12 + 2 * 9 * 4
    # idxz rows of 2J=2: nb * na summed; (0,0,0): 1 row of 1 term ...
    rows, terms = snapidx.z_row_terms(2)
    assert rows == 25
    assert counts.y_stage(2, natoms=1).flops == 10 * terms
    assert counts.force_flops(2, 1, 1) == u.flops + de.flops + 10 * terms


def test_clebsch_gordan_unit_coupling():
    # coupling with j2 = 0 is the identity; (1/2 x 1/2 -> 0) is the singlet
    assert (snapidx.clebsch_gordan(3, 0, 3)[:, 0] == 1.0).all()
    cg = snapidx.clebsch_gordan(1, 1, 0)
    assert cg[0, 1] == pytest.approx(-(2 ** -0.5))
    assert cg[1, 0] == pytest.approx(2 ** -0.5)
    assert cg[0, 0] == cg[1, 1] == 0.0


@pytest.mark.parametrize('twojmax', [8, 14])
def test_sizes_match_the_program_index(twojmax):
    from repro.core.indices import build_index
    idx = build_index(twojmax)
    assert snapidx.u_size(twojmax) == idx.idxu_max
    assert snapidx.u_half_size(twojmax) == idx.idxu_half_max
    assert len(snapidx.bispectrum_triples(twojmax)) == idx.idxb_max
    rows, terms = snapidx.z_row_terms(twojmax)
    assert rows == idx.idxz_max
    assert terms == len(idx.z_coo_dest)


def test_roofline_share_names_its_bound():
    peaks = dict(flops_per_s=1e12, hbm_bytes_per_s=1e9)
    pct, bound = counts.roofline_share(counts.StageCount(1e12, 1e6), 2.0,
                                       peaks)
    assert (pct, bound) == (50.0, 'compute')
    pct, bound = counts.roofline_share(counts.StageCount(1.0, 1e9), 4.0,
                                       peaks)
    assert (pct, bound) == (25.0, 'memory')
