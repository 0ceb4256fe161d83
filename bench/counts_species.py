"""Operations and compulsory HBM bytes of the species kernels of
multi-element SNAP (``snap_u_species``, ``snap_y_species``,
``snap_de_species``).

The work is ``counts.py``'s, counted from 2J, N and the real pairs: a
pair's element changes its cutoff and weight, not the operations of its
recursion, and an atom's element changes which coefficients its Y uses,
not how many.  The real pairs are the program's counter
``fn_cache['species_pairs']`` (the pairs inside their own cutoff
``rcut_ij``, by element pair, at the last rebuild), summed.  The bytes
add what the species path reads besides: a fifth float32 channel per
pair (its cutoff) in U and dE, and a float32 element index per atom in Y.
"""

from __future__ import annotations

import counts
import named

F32 = counts.F32


def real_pairs(species_pairs) -> int:
    """All pairs of the ``[elements, elements]`` counter."""
    return int(sum(sum(row) for row in species_pairs))


def stages(twojmax: int, natoms: int, species_pairs) -> dict:
    """{'u', 'y', 'de'} -> StageCount of the species kernels for one force
    evaluation."""
    npairs = real_pairs(species_pairs)
    base = counts.stages(twojmax, natoms, npairs)
    extra = dict(u=npairs * F32, y=natoms * F32, de=npairs * F32)
    return {k: counts.StageCount(s.flops, s.bytes + extra[k])
            for k, s in base.items()}


def kernel_roofline(ctx, prefix: str, name: str):
    """Percent of its roofline of the species kernel named ``prefix`` over
    the traced window, or None where no op carries the name or the
    program keeps no ``species_pairs`` counter."""
    c = ctx['counters']
    sec = named.kernel_seconds(ctx['trace'], prefix)
    if sec is None or not c.get('species_pairs'):
        return None
    s = stages(int(c['twojmax']), int(c['atoms']), c['species_pairs'])[name]
    n = float(c['force_evals']) / ctx['trace']['n_devices']
    pct, _ = counts.roofline_share(
        counts.StageCount(s.flops * n, s.bytes * n), sec, ctx['peaks'])
    return pct
