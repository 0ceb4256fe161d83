"""Public SNAP API: energy / force / descriptor pipelines.

Three interchangeable implementations of the force calculation:

- ``baseline``  — the pre-paper formulation (paper Listing 1/2): materialize
  Ulist, Zlist, dUlist, dBlist per (atom, neighbor); forces from
  F = -beta . dB.  O(J^5) Z storage and O(J^5) work per neighbor.
- ``adjoint``   — the paper's Sec. IV refactorization (Listing 5): compute
  the neighbor-independent adjoint Y = sum beta*Z on the fly (no Z storage),
  then the fused force contraction dE = 2 sum w Re(conj(dU) Y).
- ``autodiff``  — reverse-mode jax.grad of the energy; the paper observes the
  adjoint *is* backward differentiation, so this is an independent oracle.

All pipelines consume padded per-atom neighbor lists:
    dx, dy, dz : [natoms, nnbor]   displacements r_k - r_i
    nbr_idx    : [natoms, nnbor]   global index of neighbor atom
    mask       : [natoms, nnbor]   True for real neighbor slots

Multi-element SNAP (LAMMPS ``pair_style snap`` with ``chemflag 0``): a
config with an element table (``rcutfac``, per-element ``radii`` and
``weights``) runs the *species path* when it has more than one element.
The pipelines then take ``species`` ([natoms] element index per atom,
global under sharding), ``beta`` [nelements, ncoeff] and ``beta0``
[nelements] (or a scalar).  Pair (i, j) is cut at
``rcutfac * (R_ti + R_tj)`` (in theta0 and the switching function alike)
and enters U_i scaled by ``w_tj``; the self term stays ``wself``; atom i's
energy and adjoint Y use ``beta[t_i]``.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import bispectrum as bs
from .geometry import (compute_geometry, compute_geometry_grad,
                       sanitize_displacements)
from .indices import SnapIndex, build_index
from .ulist import compute_dulist, compute_ulist, compute_ulisttot


@dataclass(frozen=True)
class SnapConfig:
    """Hyperparameters of the SNAP descriptor (LAMMPS pair_style snap).

    Single element: ``rcut`` is the pair cutoff.  Element table: ``rcutfac``
    with one ``radii`` and ``weights`` entry per element; pair (i, j) is
    cut at ``rcutfac * (R_i + R_j)`` and ``rcut`` is derived as the largest
    of those (what neighbour lists are built at).  One element of weight
    1 is exactly the single-element config with ``rcut = rcutfac * 2R``.
    """
    twojmax: int = 8
    rcut: float = 4.67637           # TestSNAP's cutoff, recorded as is
    rmin0: float = 0.0
    rfac0: float = 0.99363
    switch_flag: bool = True
    bzero_flag: bool = True
    wself: float = 1.0
    dtype: type = jnp.float64
    rcutfac: float = 0.0            # element table: rcut_ij = rcutfac *
    radii: tuple = ()               # (R_i + R_j); neighbour j enters U_i
    weights: tuple = ()             # scaled by weights[t_j]

    def __post_init__(self):
        if not self.radii:
            if self.weights:
                raise ValueError('weights need radii (an element table)')
            return
        if len(self.weights) != len(self.radii) or self.rcutfac <= 0:
            raise ValueError('an element table needs rcutfac > 0 and one '
                             'weight per radius')
        if len(self.radii) == 1 and self.weights[0] != 1.0:
            raise ValueError('one element takes weight 1 (the '
                             'single-element path has no weight)')
        object.__setattr__(self, 'radii', tuple(map(float, self.radii)))
        object.__setattr__(self, 'weights',
                           tuple(map(float, self.weights)))
        object.__setattr__(self, 'rcut', float(self.pair_rcut.max()))

    @property
    def index(self) -> SnapIndex:
        return build_index(self.twojmax, self.wself)

    @property
    def ncoeff(self) -> int:
        return self.index.idxb_max

    @property
    def nelements(self) -> int:
        return max(1, len(self.radii))

    @property
    def species_path(self) -> bool:
        """More than one element: pairs differ in cutoff and weight."""
        return len(self.radii) > 1

    @property
    def pair_rcut(self) -> np.ndarray:
        """[nelements, nelements] pair cutoffs rcutfac * (R_i + R_j)."""
        if not self.radii:
            return np.array([[self.rcut]])
        r = np.asarray(self.radii)
        return self.rcutfac * (r[:, None] + r[None, :])

    @property
    def element_weights(self) -> np.ndarray:
        return np.asarray(self.weights or (1.0,))


# ---------------------------------------------------------------------------
# shared front end
# ---------------------------------------------------------------------------

def _lookup(table, code):
    """``table[code]`` for a small static table, as a chain of selects
    that fuse into their consumers.  As a gather, a lookup costs a TPU
    element by element whatever the table's size: 12 ms for the 1.15M
    slots of a 16,000-atom MD step on a v5e."""
    flat = np.asarray(table).reshape(-1)
    out = jnp.full(code.shape, flat[0])
    for c in range(1, flat.size):
        out = jnp.where(code == c, flat[c], out)
    return out


def species_pairs(cfg: SnapConfig, species, nbr_idx, shard=None):
    """Per-pair glue of the species path: ``(sp_i [n], w_j [n, K],
    rcut_ij [n, K])`` for the ``n`` centre rows of ``nbr_idx``.

    ``species`` is the global per-atom element index; under an atom shard
    (``(axis_name, n_shards)``) the centre rows are this shard's block,
    while ``nbr_idx`` is global either way."""
    species = jnp.asarray(species, jnp.int32)
    n = nbr_idx.shape[0]
    if shard is None:
        sp_i = species[:n]
    else:
        off = jax.lax.axis_index(shard[0]) * n
        sp_i = jax.lax.dynamic_slice_in_dim(species, off, n)
    sp_j = species[nbr_idx]
    w_j = _lookup(cfg.element_weights, sp_j)
    rc = _lookup(cfg.pair_rcut, sp_i[:, None] * cfg.nelements + sp_j)
    return sp_i, w_j, rc


def species_coefficients(cfg: SnapConfig, beta, beta0):
    """(beta [nelements, ncoeff], beta0 [nelements]) of the species path;
    a scalar beta0 is shared by every element."""
    beta = jnp.asarray(beta)
    if beta.shape != (cfg.nelements, cfg.ncoeff):
        raise ValueError(f'the species path takes beta of shape '
                         f'{(cfg.nelements, cfg.ncoeff)}, got {beta.shape}')
    beta0 = jnp.broadcast_to(jnp.asarray(beta0, beta.dtype),
                             (cfg.nelements,))
    return beta, beta0


def _require_species(cfg: SnapConfig, species):
    if cfg.species_path and species is None:
        raise ValueError(f'a config of {cfg.nelements} elements needs '
                         f'species (the element index of every atom)')
    return cfg.species_path


def _pair_geometry(cfg: SnapConfig, dx, dy, dz, mask, grad: bool,
                   pair=None):
    """Geometry of every slot.  ``pair = (w_j, rcut_ij)`` (species path)
    cuts each pair at its own cutoff and scales its switching value (and
    its derivative) by the neighbour's weight."""
    rcut = cfg.rcut
    if pair is not None:
        w_j, rcut = pair
        mask = mask & (dx * dx + dy * dy + dz * dz < rcut * rcut)
    dx, dy, dz, ok = sanitize_displacements(
        dx, dy, dz, mask, safe_r=0.5 * cfg.rcut)
    kw = dict(rcut=rcut, rmin0=cfg.rmin0, rfac0=cfg.rfac0,
              switch_flag=cfg.switch_flag)
    if grad:
        geom, dgeom = compute_geometry_grad(dx, dy, dz, **kw)
    else:
        geom, dgeom = compute_geometry(dx, dy, dz, **kw), None
    # force masked slots out of the sums entirely
    geom = geom._replace(sfac=jnp.where(ok, geom.sfac, 0.0))
    if dgeom is not None:
        dgeom = dgeom._replace(
            dsfac=jnp.where(ok[..., None], dgeom.dsfac, 0.0))
    if pair is not None:
        geom = geom._replace(sfac=geom.sfac * w_j)
        if dgeom is not None:
            dgeom = dgeom._replace(dsfac=dgeom.dsfac * w_j[..., None])
    return geom, dgeom, ok


def compute_bispectrum(cfg: SnapConfig, dx, dy, dz, mask, pair=None):
    """Descriptors B: real [natoms, ncoeff] — the fitting interface.
    ``pair``: the species path's ``(w_j, rcut_ij)``."""
    idx = cfg.index
    geom, _, ok = _pair_geometry(cfg, dx, dy, dz, mask, grad=False,
                                 pair=pair)
    u = compute_ulist(geom, idx, cfg.dtype)
    ut = compute_ulisttot(u, geom.sfac, ok, idx, cfg.wself)
    z = bs.compute_zlist(ut, idx)
    return bs.compute_blist(ut, z, idx, cfg.bzero_flag)


def snap_energy(cfg: SnapConfig, beta, beta0, dx, dy, dz, mask,
                species=None, nbr_idx=None):
    """(E_total, E_per_atom) from the linear model E_i = beta0 + beta . B_i
    (species path: ``beta[t_i]``, ``beta0[t_i]``, pairs from ``nbr_idx``)."""
    if _require_species(cfg, species):
        sp_i, w_j, rc = species_pairs(cfg, species, nbr_idx)
        beta, beta0 = species_coefficients(cfg, beta, beta0)
        b = compute_bispectrum(cfg, dx, dy, dz, mask, pair=(w_j, rc))
        e_atom = beta0[sp_i] + jnp.sum(b * beta[sp_i].astype(b.dtype), -1)
        return jnp.sum(e_atom), e_atom
    b = compute_bispectrum(cfg, dx, dy, dz, mask)
    e_atom = beta0 + b @ beta.astype(b.dtype)
    return jnp.sum(e_atom), e_atom


def assemble_forces(dedr, nbr_idx, mask, natoms, axis_name=None):
    """F_i += sum_k dE_i/dr_k ; F_k -= dE_i/dr_k (Newton's third law).

    axis_name=None: single-shard assembly — ``dedr`` rows span all
    ``natoms`` atoms and ``natoms == dedr.shape[0]``.

    axis_name='...': atom-sharded assembly inside ``shard_map`` — ``dedr``
    holds this shard's *local* atom rows, ``nbr_idx`` holds **global**
    indices, and ``natoms`` is the global count.  Each shard accumulates a
    full-length partial force array (its center-atom rows at the shard
    offset, its Newton reaction scatters wherever the neighbor lives), and
    a ``psum_scatter`` reduce-scatter sums the cross-shard (halo)
    contributions while returning only the local rows — the segment-sum
    analogue of a halo exchange.
    """
    d = dedr * mask[..., None]
    f = jnp.zeros((natoms, 3), dtype=dedr.dtype)
    if axis_name is None:
        f = f + d.sum(axis=1)                   # center rows are 0..natoms-1
        f = f.at[nbr_idx.reshape(-1)].add(-d.reshape(-1, 3))
        return f
    n_local = dedr.shape[0]
    off = jax.lax.axis_index(axis_name) * n_local
    f = f.at[off + jnp.arange(n_local)].add(d.sum(axis=1))
    f = f.at[nbr_idx.reshape(-1)].add(-d.reshape(-1, 3))
    return jax.lax.psum_scatter(f, axis_name, scatter_dimension=0,
                                tiled=True)


# ---------------------------------------------------------------------------
# adjoint pipeline (paper Sec. IV / Listing 5)
# ---------------------------------------------------------------------------

def bzero_shift(cfg: SnapConfig, beta, dtype):
    """Per-atom energy shift from the bzero self-contribution: bzero . beta.

    Shared by the jnp and kernel-layout energy contractions so the bzero
    convention has exactly one implementation.  ``beta`` [ncoeff] gives
    one shift, [rows, ncoeff] one per row.
    """
    if not cfg.bzero_flag:
        return 0.0
    idx = cfg.index
    bz = jnp.asarray([idx.bzero[t[2]] for t in idx.idxb_triples], dtype)
    if beta.ndim == 2:
        return beta.astype(dtype) @ bz
    return bz @ beta.astype(dtype)


def energy_from_ylist(cfg: SnapConfig, ulisttot, ylist, beta, beta0):
    """Per-atom energy directly from the adjoint:

        sum_l beta_l B_l  ==  (2/3) sum_jju w_jju Re(conj(U) Y)

    Each bispectrum triple is distributed into Y three times (once per index
    permutation) with weights that make every copy contribute the same
    contraction value, hence the 1/3.  Verified against the Z-path to 1e-14.
    This removes the O(J^5) Z stage from the MD energy path entirely —
    a beyond-paper optimization enabled by the adjoint refactorization.
    """
    idx = cfg.index
    e_raw = (2.0 / 3.0) * jnp.sum(
        idx.dedr_weight * (ulisttot.real * ylist.real
                           + ulisttot.imag * ylist.imag), axis=-1)
    return beta0 + e_raw - bzero_shift(cfg, beta, e_raw.dtype)


def energy_forces_adjoint(cfg: SnapConfig, beta, beta0, dx, dy, dz,
                          nbr_idx, mask, with_energy: bool = True,
                          energy_via_z: bool = False, shard=None,
                          atom_chunks: int = 1, species=None):
    """The paper's refactored pipeline: U -> Y -> fused dE -> forces.

    shard: optional ``(axis_name, n_shards)`` when running as the per-shard
    body of an atom-sharded ``shard_map`` — rows are local atoms, nbr_idx is
    global, and force assembly reduce-scatters across shards.  The returned
    energy is then this shard's partial sum (the wrapper psums it).

    atom_chunks: run the per-atom stages (U, Y, dE, energy) over this many
    equal blocks of atom rows in turn (``lax.map``), so that only one
    block's per-pair planes are live — what lets the f64 oracle run at
    the paper's 2000-atom 2J=14 size in host memory.  Forces are assembled
    once from all blocks; natoms must divide by ``atom_chunks``.

    species: the element index of every atom (species path; see the module
    docstring), ignored by a single-element config.
    """
    idx = cfg.index
    natoms, nnbor = dx.shape
    axis_name, n_shards = shard if shard is not None else (None, 1)
    per_row = None
    if _require_species(cfg, species):
        with jax.named_scope('snap.species'):
            sp_i, w_j, rc = species_pairs(cfg, species, nbr_idx, shard)
            beta_s, beta0_s = species_coefficients(cfg, beta, beta0)
            per_row = (w_j, rc, beta_s[sp_i], beta0_s[sp_i])

    def rows(dx, dy, dz, mask, per_row):
        if per_row is None:
            pair, b, b0 = None, beta, beta0
        else:
            w_j, rc, b, b0 = per_row
            pair = (w_j, rc)
        geom, dgeom, ok = _pair_geometry(cfg, dx, dy, dz, mask, grad=True,
                                         pair=pair)
        u, du = compute_dulist(geom, dgeom, idx, cfg.dtype)
        ut = compute_ulisttot(u, geom.sfac, ok, idx, cfg.wself)
        y = bs.compute_ylist(ut, b, idx)
        n = dx.shape[0]
        atom_of_pair = jnp.repeat(jnp.arange(n), nnbor)
        dedr = bs.compute_dedr(
            du.reshape(-1, 3, idx.idxu_max), y, atom_of_pair, idx)
        e_atom = None
        if with_energy and energy_via_z:
            z = bs.compute_zlist(ut, idx)
            bl = bs.compute_blist(ut, z, idx, cfg.bzero_flag)
            if per_row is None:
                e_atom = b0 + bl @ b.astype(bl.dtype)
            else:
                e_atom = b0 + jnp.sum(bl * b.astype(bl.dtype), -1)
        elif with_energy:
            e_atom = energy_from_ylist(cfg, ut, y, b, b0)
        return dedr.reshape(n, nnbor, 3), ok, e_atom

    if atom_chunks == 1:
        dedr, ok, e_atom = rows(dx, dy, dz, mask, per_row)
    else:
        if natoms % atom_chunks:
            raise ValueError(f'natoms={natoms} must divide by '
                             f'atom_chunks={atom_chunks}')
        blocks = jax.tree.map(
            lambda a: a.reshape(atom_chunks, -1, *a.shape[1:]),
            (dx, dy, dz, mask, per_row))
        out = jax.lax.map(lambda a: rows(*a), blocks)
        dedr, ok, e_atom = jax.tree.map(
            lambda a: a.reshape(natoms, *a.shape[2:]), out)
    forces = assemble_forces(dedr, nbr_idx, ok, natoms * n_shards,
                             axis_name=axis_name)
    if not with_energy:
        return None, None, forces
    return jnp.sum(e_atom), e_atom, forces


# ---------------------------------------------------------------------------
# baseline pipeline (paper Listing 1/2: store Z, dU, dB)
# ---------------------------------------------------------------------------

def energy_forces_baseline(cfg: SnapConfig, beta, beta0, dx, dy, dz,
                           nbr_idx, mask, db_chunks: int = 8, shard=None,
                           species=None):
    """Pre-refactorization formulation: materializes Zlist and dBlist.
    Single element only (the species path runs on adjoint and kernel)."""
    if _require_species(cfg, species):
        raise ValueError("impl='baseline' is single-element; use 'adjoint' "
                         "or 'kernel' for a multi-element config")
    idx = cfg.index
    natoms, nnbor = dx.shape
    axis_name, n_shards = shard if shard is not None else (None, 1)
    geom, dgeom, ok = _pair_geometry(cfg, dx, dy, dz, mask, grad=True)
    u, du = compute_dulist(geom, dgeom, idx, cfg.dtype)
    ut = compute_ulisttot(u, geom.sfac, ok, idx, cfg.wself)
    zlist = bs.compute_zlist(ut, idx)                   # O(J^5) storage
    atom_of_pair = jnp.repeat(jnp.arange(natoms), nnbor)
    du_flat = du.reshape(-1, 3, idx.idxu_max)
    # dBlist: [P, 3, ncoeff] — the memory blow-up of paper Fig. 1
    db = _compute_dblist_chunked(du_flat, zlist, atom_of_pair, idx,
                                 db_chunks)
    dedr = jnp.einsum('pkl,l->pk', db, beta.astype(db.dtype))
    forces = assemble_forces(dedr.reshape(natoms, nnbor, 3), nbr_idx, ok,
                             natoms * n_shards, axis_name=axis_name)
    b = bs.compute_blist(ut, zlist, idx, cfg.bzero_flag)
    e_atom = beta0 + b @ beta.astype(b.dtype)
    return jnp.sum(e_atom), e_atom, forces


def _compute_dblist_chunked(du_flat, zlist, atom_of_pair, idx, nchunk):
    nnz = idx.db_coo_dest.shape[0]
    out = jnp.zeros((du_flat.shape[0], 3, idx.idxb_max),
                    dtype=du_flat.real.dtype)
    z_at = zlist[atom_of_pair]
    bounds = np.linspace(0, nnz, nchunk + 1).astype(int)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        if hi == lo:
            continue
        z = z_at[:, idx.db_coo_zsrc[lo:hi]]
        du = du_flat[:, :, idx.db_coo_dusrc[lo:hi]]
        contrib = idx.db_coo_w[lo:hi] * (
            du.real * z.real[:, None, :] + du.imag * z.imag[:, None, :])
        out = out.at[:, :, idx.db_coo_dest[lo:hi]].add(contrib)
    return out


# ---------------------------------------------------------------------------
# autodiff oracle
# ---------------------------------------------------------------------------

def make_energy_fn(cfg: SnapConfig, beta, beta0, nbr_idx, shifts, mask,
                   species=None):
    """E(positions) with fixed neighbor topology and periodic image shifts.

    shifts: [natoms, nnbor, 3] constant image offsets such that
    r_k - r_i = positions[nbr_idx] + shifts - positions[:, None].
    """
    def energy(positions):
        disp = positions[nbr_idx] + shifts - positions[:, None, :]
        e, _ = snap_energy(cfg, beta, beta0,
                           disp[..., 0], disp[..., 1], disp[..., 2], mask,
                           species=species, nbr_idx=nbr_idx)
        return e
    return energy


def energy_forces_autodiff(cfg: SnapConfig, beta, beta0, positions,
                           nbr_idx, shifts, mask, species=None):
    """Independent oracle: F = -grad E via reverse-mode AD."""
    efn = make_energy_fn(cfg, beta, beta0, nbr_idx, shifts, mask, species)
    e, grad = jax.value_and_grad(efn)(positions)
    return e, -grad


IMPLEMENTATIONS = ('baseline', 'adjoint', 'kernel')


def energy_forces(cfg: SnapConfig, beta, beta0, dx, dy, dz, nbr_idx, mask,
                  impl: str = 'adjoint', **kw):
    """Dispatch front-end used by MD / benchmarks.

    impl='kernel' extras (forwarded to ``snap_force_pipeline``):
    ``layout='half'|'full'`` selects the symmetric half-index planes
    (default) vs the v1 full planes, ``y_tile`` sizes the Y kernel's COO
    tiles, and ``mxu_dtype`` (e.g. ``jnp.bfloat16``) rounds the Y walk's
    operands while accumulation stays in ``dtype``.

    A multi-element config takes ``species=`` (every impl but baseline).
    """
    if impl == 'adjoint':
        return energy_forces_adjoint(cfg, beta, beta0, dx, dy, dz,
                                     nbr_idx, mask, **kw)
    if impl == 'baseline':
        return energy_forces_baseline(cfg, beta, beta0, dx, dy, dz,
                                      nbr_idx, mask, **kw)
    if impl == 'kernel':
        from repro.kernels import ops as kops
        return kops.snap_force_pipeline(cfg, beta, beta0, dx, dy, dz,
                                        nbr_idx, mask, **kw)
    raise ValueError(f'unknown impl {impl!r}; choose from {IMPLEMENTATIONS}')
