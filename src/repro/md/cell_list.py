"""On-device linked-cell neighbor engine (fully ``jit``-able, fixed shapes).

The host builders in :mod:`repro.md.neighbor` are the oracle; this module is
the production path: every rebuild runs as traced JAX ops with **static
shapes**, so the whole MD loop — integration, displacement trigger, and the
rebuild itself — stays inside one ``jax.jit`` boundary (the LAMMPS-KOKKOS
"build neighbor lists on device" pattern).

Fixed-shape layout
------------------
Atoms are binned into a static ``[ncells, cell_cap]`` table by sorting atom
indices by flat bin id (``argsort`` + ``searchsorted`` rank-within-bin, a
device-friendly counting sort).  Candidates come from a **deduplicated**
27-stencil gather (offsets collapse mod nbins, so boxes with < 3 bins along
an axis never revisit a cell), made once a cell and shared by its atoms;
packing valid pairs to the front of the padded ``[N, K]`` lists is a
stable sort of the candidates on their invalid flag.

Overflow contract
-----------------
``jit`` cannot raise, so capacity violations (cell_cap, max_nbors) come back
as *flags* — int32 ``[nbr_count_max, cell_count_max]`` — carried as running
maxima through the device loop and checked at segment boundaries, where
:func:`check_flags` raises the same :class:`NeighborOverflowError` the host
builders do (or :class:`CellOverflowError` for bin-capacity overflow).

Skin radius
-----------
Lists are built with cutoff ``rcut + skin``; they stay sufficient for the
exact ``rcut`` pair set until any atom has moved more than ``skin / 2``
since the build (each of two atoms moving < skin/2 closes a pair gap by
< skin).  The consumer applies a per-step hard cut at ``rcut`` (see
``md/integrate.py``), which also keeps the ``theta0 = pi`` Cayley-Klein
singularity just beyond ``rcut`` out of the force kernels.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np

from .neighbor import (NeighborOverflowError, dedup_stencil,
                       suggest_capacity)

# Health-flag lattice layout (int32 vector carried through the device
# loop; slots 0-1 are running max *counts* from the neighbor build, slots
# 2-5 are sticky 0/1 indicators set by the in-scan guards of
# md/integrate.py).  The host reads the whole vector once per chunk —
# the same readback that already returns the logging rows, so the guards
# add no extra syncs.
FLAG_NBR_MAX = 0      # max neighbors seen by any atom (vs grid.max_nbors)
FLAG_CELL_MAX = 1     # max cell occupancy seen (vs grid.cell_cap)
FLAG_NAN_FORCE = 2    # non-finite value in the force array
FLAG_NAN_STATE = 3    # non-finite value in positions or velocities
FLAG_ESCAPE = 4       # an atom left the box by > escape_factor box lengths
FLAG_DRIFT = 5        # |Etot - Eref| exceeded the watchdog bound
N_FLAGS = 6


class CellOverflowError(RuntimeError):
    """A cell holds more atoms than the static cell_cap slots."""

    def __init__(self, max_count, cell_cap):
        self.max_count = int(max_count)
        self.cell_cap = int(cell_cap)
        self.suggested = suggest_capacity(self.max_count)
        super().__init__(
            f'cell list overflow: a cell holds {self.max_count} atoms but '
            f'capacity cell_cap={self.cell_cap}; retry with '
            f'cell_cap={self.suggested} '
            f'(observed max {self.max_count} + headroom)')


@dataclass(frozen=True)
class CellGrid:
    """Static (hashable) configuration of the device cell list.

    Everything that determines array *shapes* lives here so the grid can be
    a ``jax.jit`` static argument / closure constant: bin counts, cell
    capacity, padded list width, and the deduplicated stencil.
    """
    nbins: tuple          # (nx, ny, nz) bins, each >= 1
    cell_cap: int         # atoms per cell slot count (static)
    max_nbors: int        # K: padded neighbor-list width (static)
    rcut: float           # force cutoff
    skin: float           # Verlet skin; build cutoff is rcut + skin
    stencil: tuple        # deduplicated 27-stencil offsets

    @property
    def ncells(self) -> int:
        return self.nbins[0] * self.nbins[1] * self.nbins[2]

    @property
    def rcut_build(self) -> float:
        return self.rcut + self.skin


def make_grid(box, rcut, skin=0.0, cell_cap=16, max_nbors=64) -> CellGrid:
    """Build the static grid config for a (fixed) box.

    Bin edges are >= rcut + skin so the deduplicated 27-stencil covers every
    candidate pair; degenerate boxes (< 3 bins along an axis) degrade
    gracefully to fewer, larger cells.
    """
    box = np.asarray(box, np.float64)
    rb = float(rcut) + float(skin)
    nbins = tuple(int(max(1, np.floor(b / rb))) for b in box)
    return CellGrid(nbins=nbins, cell_cap=int(cell_cap),
                    max_nbors=int(max_nbors), rcut=float(rcut),
                    skin=float(skin), stencil=tuple(dedup_stencil(nbins)))


def auto_cell_cap(pos, box, rcut_build, headroom=1.5, pad=4) -> int:
    """Host-side one-shot sizing of cell_cap from the initial configuration.

    O(N) numpy bincount; the returned capacity carries ``headroom`` +
    ``pad`` margin for density fluctuations during the run (violations are
    still caught by the overflow flags).
    """
    box = np.asarray(box, np.float64)
    nbins = np.maximum(1, np.floor(box / rcut_build).astype(int))
    frac = np.asarray(pos) / box
    frac -= np.floor(frac)
    b = np.minimum((frac * nbins).astype(int), nbins - 1)
    flat = (b[:, 0] * nbins[1] + b[:, 1]) * nbins[2] + b[:, 2]
    occ = int(np.bincount(flat, minlength=int(nbins.prod())).max())
    return int(np.ceil(occ * headroom)) + pad


def _bin_atoms(pos, box, grid: CellGrid):
    """Sort-by-bin into the static [ncells, cell_cap] table.

    Returns (table, bin_coords, cell_count_max).  Table entries are atom
    indices, with N as the empty-slot sentinel; atoms beyond cell_cap in a
    cell are dropped into a discard slot and reported via the count.
    """
    N = pos.shape[0]
    nb = jnp.asarray(grid.nbins, jnp.int32)
    frac = pos / box
    frac = frac - jnp.floor(frac)                   # wrap into [0, 1)
    b = jnp.minimum((frac * nb).astype(jnp.int32), nb - 1)
    flat = (b[:, 0] * grid.nbins[1] + b[:, 1]) * grid.nbins[2] + b[:, 2]
    order = jnp.argsort(flat).astype(jnp.int32)
    sorted_flat = flat[order]
    starts = jnp.searchsorted(sorted_flat,
                              jnp.arange(grid.ncells, dtype=jnp.int32))
    rank = jnp.arange(N, dtype=jnp.int32) - starts[sorted_flat]
    cap = grid.cell_cap
    slot = jnp.where(rank < cap, sorted_flat * cap + rank,
                     grid.ncells * cap)             # overflow -> discard slot
    table = jnp.full(grid.ncells * cap + 1, N, jnp.int32).at[slot].set(order)
    counts = jnp.zeros(grid.ncells, jnp.int32).at[flat].add(1)
    return table[:-1].reshape(grid.ncells, cap), b, counts.max()


@lru_cache(maxsize=32)
def _stencil_cells(grid: CellGrid) -> np.ndarray:
    """[ncells, S] flat ids of every cell's stencil cells, in stencil
    order (static: a function of the grid alone)."""
    nb = np.asarray(grid.nbins)
    b = np.stack(np.unravel_index(np.arange(grid.ncells), grid.nbins), 1)
    cells = (b[:, None, :] + np.asarray(grid.stencil)[None]) % nb
    return ((cells[..., 0] * nb[1] + cells[..., 1]) * nb[2]
            + cells[..., 2]).astype(np.int32)


def device_neighbors(pos, box, grid: CellGrid):
    """Fixed-shape neighbor build, entirely traced (no host sync).

    Returns ``(nbr_idx [N, K] int32, mask [N, K] bool, shifts [N, K, 3],
    flags [2] int32)`` with ``flags = [max neighbor count, max cell
    occupancy]`` — compare against ``grid.max_nbors`` / ``grid.cell_cap``
    via :func:`check_flags` at the next host boundary.

    ``shifts`` satisfy ``disp = pos[nbr_idx] + shifts - pos[:, None]``
    exactly for the *raw* (possibly unwrapped) positions, so the MD loop can
    recompute displacements on device as atoms drift out of the box.

    Every atom of a cell has the same candidates, so they are gathered
    once a cell (``[ncells, S*cap]`` slots) and handed to each atom as a
    whole row; packing sorts the candidate indices along with the invalid
    flag, and the kept pairs' shifts are recomputed from their positions.
    Element gathers over all ``N * S * cap`` candidates, which a TPU pays
    for one by one, would cost several times the rest of the build.
    """
    N = pos.shape[0]
    table, b, cell_max = _bin_atoms(pos, box, grid)
    flat = (b[:, 0] * grid.nbins[1] + b[:, 1]) * grid.nbins[2] + b[:, 2]
    stencil = jnp.asarray(_stencil_cells(grid))           # [ncells, S]
    pos_pad = jnp.concatenate([pos, jnp.zeros((1, 3), pos.dtype)])
    cell_cand = table[stencil].reshape(grid.ncells, -1)   # [ncells, S*cap]
    cell_pos = jnp.transpose(pos_pad[table][stencil], (0, 3, 1, 2)).reshape(
        grid.ncells, 3, -1)                               # [ncells, 3, S*cap]
    cand = cell_cand[flat]                                # [N, S*cap]
    d = cell_pos[flat] - pos[:, :, None]                  # [N, 3, S*cap]
    shift = -box[:, None] * jnp.round(d / box[:, None])
    dd = d + shift
    r2 = jnp.sum(dd * dd, axis=1)
    rb2 = grid.rcut_build * grid.rcut_build
    valid = ((cand != jnp.arange(N, dtype=jnp.int32)[:, None])
             & (cand < N) & (r2 < rb2))
    counts = valid.sum(axis=1)
    # pack valid candidates to the front: stable sort on the invalid flag
    key, packed = jax.lax.sort(
        (jnp.logical_not(valid).astype(jnp.int32), cand), dimension=1,
        is_stable=True, num_keys=1)
    mask = key[:, :grid.max_nbors] == 0
    nbr_idx = jnp.where(mask, packed[:, :grid.max_nbors], 0)
    d = pos_pad[nbr_idx] - pos[:, None, :]
    shifts = jnp.where(mask[..., None], -box * jnp.round(d / box), 0.0)
    flags = jnp.stack([counts.max().astype(jnp.int32),
                       cell_max.astype(jnp.int32)])
    return nbr_idx, mask, shifts, flags


def brute_neighbors_device(pos, box, rcut, max_nbors: int, n_valid=None):
    """Fixed-shape traced O(N^2) neighbor build for one configuration.

    The serving counterpart of :func:`device_neighbors`: no grid statics
    at all (the box is a *traced* value, so one compiled function serves
    every box in a shape bucket), which makes it ``vmap``-able over a
    batch of heterogeneous configurations — the per-bucket batched force
    entry in :mod:`repro.kernels.ops` relies on exactly that.

    ``n_valid`` (traced scalar) masks trailing padding atoms out of the
    pair set, so one static ``[n_pad, K]`` shape serves every request
    size up to ``n_pad``.  Like :func:`device_neighbors`, capacity
    violations come back as count *flags* (slot ``FLAG_NBR_MAX``; the
    cell slot stays 0 — there is no cell table here), never as silent
    truncation: when the count exceeds ``max_nbors`` the packed list is
    incomplete and the consumer must treat the lane as failed.  Non-finite
    positions never produce pairs (NaN compares false), so a poisoned
    configuration degrades to an empty pair set — detection is the force
    layer's input/output finiteness flags, and the poison cannot spread
    past its own lane.

    Returns ``(nbr_idx [N, K] int32, mask [N, K] bool, disp [N, K, 3],
    flags [2] int32)`` with ``disp = pos[nbr] - pos[i]`` minimum-imaged.
    """
    N = pos.shape[0]
    iota = jnp.arange(N, dtype=jnp.int32)
    nv = jnp.asarray(N if n_valid is None else n_valid, jnp.int32)
    ok_atom = iota < nv
    d = pos[None, :, :] - pos[:, None, :]
    dd = d - box * jnp.round(d / box)
    r2 = jnp.sum(dd * dd, axis=-1)
    within = ((iota[None, :] != iota[:, None])
              & ok_atom[None, :] & ok_atom[:, None]
              & (r2 < rcut * rcut))
    counts = within.sum(axis=1)
    # pack valid candidates to the front (stable sort on the invalid flag,
    # same idiom as device_neighbors) and truncate to the static width
    key = jnp.logical_not(within).astype(jnp.int32)
    ordk = jnp.argsort(key, axis=1)[:, :max_nbors].astype(jnp.int32)
    mask = jnp.take_along_axis(within, ordk, axis=1)
    nbr_idx = jnp.where(mask, ordk, 0)
    disp = jnp.where(mask[..., None],
                     jnp.take_along_axis(dd, ordk[..., None], axis=1), 0.0)
    flags = jnp.stack([counts.max().astype(jnp.int32),
                       jnp.zeros((), jnp.int32)])
    return nbr_idx, mask, disp, flags


def check_flags(flags, grid: CellGrid):
    """Host-boundary overflow check, mirroring the host builders' raises.

    Accepts either the bare ``[2]`` build flags or the full ``[N_FLAGS]``
    health vector (only the capacity slots are checked here; the sticky
    health slots are the recovery layer's business — see
    :mod:`repro.md.resilience`).
    """
    f = np.asarray(flags)
    nbr_max = int(f[FLAG_NBR_MAX])
    cell_max = int(f[FLAG_CELL_MAX])
    if cell_max > grid.cell_cap:
        raise CellOverflowError(cell_max, grid.cell_cap)
    if nbr_max > grid.max_nbors:
        raise NeighborOverflowError(nbr_max, grid.max_nbors)


@lru_cache(maxsize=32)
def jitted_build(grid: CellGrid):
    """Process-wide cache of the jitted build, one entry per static grid."""
    return jax.jit(partial(device_neighbors, grid=grid))


def cell_neighbors_device(pos, box, rcut, max_nbors=64, skin=0.0,
                          cell_cap=None):
    """Host-facing wrapper with the same contract as the host builders.

    Builds on device, syncs once, raises on overflow.  Returns
    ``(nbr_idx, mask, disp, shifts)`` like ``brute_neighbors`` — the parity
    surface for tests and the A/B oracle comparison.
    """
    pos = np.asarray(pos, np.float64)
    box = np.asarray(box, np.float64)
    if cell_cap is None:
        cell_cap = auto_cell_cap(pos, box, rcut + skin)
    grid = make_grid(box, rcut, skin, cell_cap, max_nbors)
    nbr_idx, mask, shifts, flags = jitted_build(grid)(
        jnp.asarray(pos), jnp.asarray(box))
    check_flags(flags, grid)
    nbr_idx = np.asarray(nbr_idx)
    mask = np.asarray(mask)
    shifts = np.asarray(shifts)
    disp = np.where(mask[..., None],
                    pos[nbr_idx] + shifts - pos[:, None, :], 0.0)
    return nbr_idx, mask, disp, shifts
