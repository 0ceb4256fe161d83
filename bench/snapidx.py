"""SNAP index sets, enumerated from 2J alone (LAMMPS ``sna.cpp`` conventions).

The benchmark's own copy: ``counts.py`` and ``reference.py`` read these
tables and nothing of the program under test, so a rewrite of the
program's kernels or index tables cannot change what the benchmark counts
or what it compares against.

All ``j`` are doubled angular momenta (integers 2j).

- ``idxu``: the (j+1) x (j+1) Wigner-U layers, j = 0..2J, row-major
  (mb, ma) within a layer.  The "half" layers are the rows 2mb <= j that
  the recursion computes; the other rows follow from the mirror
  u(j-mb, j-ma) = (-1)^(mb+ma) conj(u(mb, ma)).
- CG triples: (j1, j2, j) with j1 >= j2, |j1-j2| <= j <= min(2J, j1+j2),
  j - j1 - j2 even.
- ``idxz`` rows: (j1, j2, j, mb, ma) over every triple with 2mb <= j.
  Each row is a double sum over nb * na CG pairs (LAMMPS compute_zi).
- ``idxb``: the triples with j >= j1 >= j2, one bispectrum component each.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import lru_cache

import numpy as np


def triples(twojmax: int):
    return [(j1, j2, j)
            for j1 in range(twojmax + 1)
            for j2 in range(j1 + 1)
            for j in range(j1 - j2, min(twojmax, j1 + j2) + 1, 2)]


def bispectrum_triples(twojmax: int):
    return [t for t in triples(twojmax) if t[2] >= t[0]]


def u_offset(j: int) -> int:
    """Start of layer j in the flattened full U storage."""
    return sum((k + 1) ** 2 for k in range(j))


def u_size(twojmax: int) -> int:
    return u_offset(twojmax + 1)


def u_half_size(twojmax: int) -> int:
    return sum((j // 2 + 1) * (j + 1) for j in range(twojmax + 1))


def _fact(n: int) -> float:
    return float(math.factorial(n))


def clebsch_gordan(j1: int, j2: int, j: int) -> np.ndarray:
    """cg[m1, m2] for coupling (j1, m1) x (j2, m2) into (j, m), with
    m = (2m1 - j1 + 2m2 - j2 + j) / 2; zero where m falls outside [0, j].
    LAMMPS ``SNA::init_clebsch_gordan``."""
    out = np.zeros((j1 + 1, j2 + 1))
    dcg = math.sqrt(_fact((j1 + j2 - j) // 2) * _fact((j1 - j2 + j) // 2)
                    * _fact((-j1 + j2 + j) // 2)
                    / _fact((j1 + j2 + j) // 2 + 1))
    for m1 in range(j1 + 1):
        aa2 = 2 * m1 - j1
        for m2 in range(j2 + 1):
            bb2 = 2 * m2 - j2
            m = (aa2 + bb2 + j) // 2
            if m < 0 or m > j:
                continue
            lo = max(0, -(j - j2 + aa2) // 2, -(j - j1 - bb2) // 2)
            hi = min((j1 + j2 - j) // 2, (j1 - aa2) // 2, (j2 + bb2) // 2)
            s = 0.0
            for z in range(lo, hi + 1):
                s += (-1.0) ** z / (
                    _fact(z) * _fact((j1 + j2 - j) // 2 - z)
                    * _fact((j1 - aa2) // 2 - z) * _fact((j2 + bb2) // 2 - z)
                    * _fact((j - j2 + aa2) // 2 + z)
                    * _fact((j - j1 - bb2) // 2 + z))
            cc2 = 2 * m - j
            sfac = math.sqrt(_fact((j1 + aa2) // 2) * _fact((j1 - aa2) // 2)
                             * _fact((j2 + bb2) // 2) * _fact((j2 - bb2) // 2)
                             * _fact((j + cc2) // 2) * _fact((j - cc2) // 2)
                             * (j + 1))
            out[m1, m2] = s * dcg * sfac
    return out


def _m_range(m: int, j1: int, j2: int, j: int):
    """(m1min, m2max, n) of the CG sum for target index m (LAMMPS
    ``init_index``: ma1min, ma2max, na)."""
    m1min = max(0, (2 * m - j - j2 + j1) // 2)
    m2max = (2 * m - j - (2 * m1min - j1) + j2) // 2
    n = min(j1, (2 * m - j + j2 + j1) // 2) - m1min + 1
    return m1min, m2max, n


def half_weight(j: int, mb: int, ma: int) -> float:
    """LAMMPS compute_bi's half-plane weights: rows 2mb < j count once,
    the middle row of an even layer counts for ma < j/2 and half at
    ma = j/2; the factor 2 is applied by the caller."""
    if 2 * mb < j:
        return 1.0
    if 2 * mb == j:
        if 2 * ma < j:
            return 1.0
        if 2 * ma == j:
            return 0.5
    return 0.0


@dataclass(frozen=True)
class ZTerms:
    """One entry per term of the CG double sums of every idxz row whose
    triple is a bispectrum triple, with the B weight folded in:

        B[l] = sum_{terms t of l} w[t] Re(conj(U[d[t]]) U[s1[t]] U[s2[t]])

    where U is the per-atom total in full storage.
    """
    comp: np.ndarray    # [T] bispectrum component l
    d: np.ndarray       # [T] full U index of (j, mb, ma)
    s1: np.ndarray      # [T] full U index of (j1, mb1, ma1)
    s2: np.ndarray      # [T] full U index of (j2, mb2, ma2)
    w: np.ndarray       # [T] 2 * half_weight * cg(mb1,mb2) * cg(ma1,ma2)


@lru_cache(maxsize=8)
def z_row_terms(twojmax: int):
    """Number of CG terms (nb * na) of every idxz row, over all triples:
    the work of the adjoint Y (LAMMPS compute_yi/compute_zi)."""
    n = 0
    rows = 0
    for (j1, j2, j) in triples(twojmax):
        for mb in range(j // 2 + 1):
            nb = _m_range(mb, j1, j2, j)[2]
            for ma in range(j + 1):
                na = _m_range(ma, j1, j2, j)[2]
                n += nb * na
                rows += 1
    return rows, n


@lru_cache(maxsize=8)
def bispectrum_terms(twojmax: int) -> ZTerms:
    comp, d, s1, s2, w = [], [], [], [], []
    for l, (j1, j2, j) in enumerate(bispectrum_triples(twojmax)):
        cg = clebsch_gordan(j1, j2, j)
        for mb in range(j // 2 + 1):
            mb1min, mb2max, nb = _m_range(mb, j1, j2, j)
            for ma in range(j + 1):
                hw = half_weight(j, mb, ma)
                if hw == 0.0:
                    continue
                ma1min, ma2max, na = _m_range(ma, j1, j2, j)
                for ib in range(nb):
                    mb1, mb2 = mb1min + ib, mb2max - ib
                    for ia in range(na):
                        ma1, ma2 = ma1min + ia, ma2max - ia
                        c = cg[mb1, mb2] * cg[ma1, ma2]
                        if c == 0.0:
                            continue
                        comp.append(l)
                        d.append(u_offset(j) + mb * (j + 1) + ma)
                        s1.append(u_offset(j1) + mb1 * (j1 + 1) + ma1)
                        s2.append(u_offset(j2) + mb2 * (j2 + 1) + ma2)
                        w.append(2.0 * hw * c)
    i32 = np.int32
    return ZTerms(np.asarray(comp, i32), np.asarray(d, i32),
                  np.asarray(s1, i32), np.asarray(s2, i32),
                  np.asarray(w, np.float64))
