"""Operations and compulsory HBM bytes of the SNAP force stages.

Worked out from 2J, the number of atoms N and the number of real
neighbours (pairs inside rcut) alone, with the index sets enumerated by
``snapidx``.  Nothing here reads the program's COO tables or HLO, so the
count is the same work whatever implements it, and a kernel rewrite
cannot make it stale.

Operations are real floating-point operations of the algorithm in its
plain form (LAMMPS ``compute_ui``, ``compute_yi``, ``compute_duidrj`` +
``compute_deidrj``): a complex multiply is 6, a complex add 2, a real
times a complex 2.

- U (``compute_ui``): per pair, every element of the rows 2mb <= j of
  layers j = 1..2J takes an a-term and a b-term (coefficient times
  conj(a) times u: 8 each) and their sum (2): 18.  Accumulating the
  switching-weighted pair into the atom's total costs 4 per half element.
- Y (``compute_yi``): per atom, every term of the CG double sums of every
  idxz row: u1 * u2 (6), times the CG product (2), accumulated (2): 10.
- dE (``compute_duidrj`` + ``compute_deidrj``): per pair, per half
  element, the U recursion (18) with its three tangents (each: two
  complex multiplies, a coefficient and the sum, 34), the switching
  chain rule (6 per direction) and the contraction with Y (4 per
  direction): 18 + 3 * (34 + 6 + 4) = 150.

Bytes are each stage's canonical inputs read once and its outputs written
once, in float32: a pair's displacement and mask (16 B); an atom's half
U or Y planes (2 * u_half * 4 B); a pair's dE/dr (12 B).
"""

from __future__ import annotations

from dataclasses import dataclass

import snapidx

F32 = 4


@dataclass(frozen=True)
class StageCount:
    flops: float
    bytes: float


def _left_elements(twojmax: int) -> int:
    """Elements of the rows 2mb <= j over layers j = 1..2J."""
    return sum((j // 2 + 1) * (j + 1) for j in range(1, twojmax + 1))


def u_stage(twojmax: int, natoms: int, npairs: int) -> StageCount:
    half = snapidx.u_half_size(twojmax)
    flops = npairs * (18 * _left_elements(twojmax) + 4 * half)
    bytes_ = npairs * 16 + natoms * 2 * half * F32
    return StageCount(float(flops), float(bytes_))


def y_stage(twojmax: int, natoms: int) -> StageCount:
    _, terms = snapidx.z_row_terms(twojmax)
    half = snapidx.u_half_size(twojmax)
    flops = natoms * 10 * terms
    bytes_ = natoms * 2 * (2 * half * F32)
    return StageCount(float(flops), float(bytes_))


def de_stage(twojmax: int, natoms: int, npairs: int) -> StageCount:
    half = snapidx.u_half_size(twojmax)
    flops = npairs * 150 * _left_elements(twojmax)
    bytes_ = npairs * (16 + 12) + natoms * 2 * half * F32
    return StageCount(float(flops), float(bytes_))


def stages(twojmax: int, natoms: int, npairs: int) -> dict:
    """{'u', 'y', 'de'} -> StageCount for one force evaluation."""
    return dict(u=u_stage(twojmax, natoms, npairs),
                y=y_stage(twojmax, natoms),
                de=de_stage(twojmax, natoms, npairs))


def force_flops(twojmax: int, natoms: int, npairs: int) -> float:
    """Algorithmic operations of one whole force evaluation."""
    return sum(s.flops for s in stages(twojmax, natoms, npairs).values())


def roofline_share(count: StageCount, seconds: float, peaks: dict) -> tuple:
    """(percent of the roofline, 'compute' | 'memory'): the least time
    the chip could take for ``count`` over the measured ``seconds``."""
    t_flop = count.flops / peaks['flops_per_s']
    t_mem = count.bytes / peaks['hbm_bytes_per_s']
    bound = 'compute' if t_flop >= t_mem else 'memory'
    return 100.0 * max(t_flop, t_mem) / seconds, bound
