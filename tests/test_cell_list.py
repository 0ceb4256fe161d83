"""Device cell-list engine: parity vs the host oracle, overflow-flag
semantics, skin-trigger correctness, and the zero-host-transfer contract
of the ``loop='device'`` MD driver."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.snap import SnapConfig
from repro.md.cell_list import (CellOverflowError, cell_neighbors_device,
                                make_grid)
from repro.md.integrate import MDState, init_velocities, run_nve
from repro.md.lattice import bcc_lattice, paper_box, perturb
from repro.md.neighbor import NeighborOverflowError, brute_neighbors


def _pair_sets(nbr_idx, mask):
    return [set(nbr_idx[i, mask[i]].tolist()) for i in range(len(nbr_idx))]


def test_device_matches_brute_pair_sets():
    """Same pair set as the O(N^2) oracle, up to slot permutation."""
    pos, box = paper_box(natoms=250)
    pos = perturb(pos, 0.08, seed=1)
    b = brute_neighbors(pos, box, 4.0, max_nbors=40)
    d = cell_neighbors_device(pos, box, 4.0, max_nbors=40)
    assert _pair_sets(*b[:2]) == _pair_sets(*d[:2])
    np.testing.assert_allclose(np.sort(b[2][b[1]].ravel()),
                               np.sort(d[2][d[1]].ravel()), atol=1e-12)


def test_device_small_box_no_duplicates():
    """nbins < 3 along an axis: the deduplicated stencil must not revisit
    a cell (the aliasing that double-counted pairs in the host builder)."""
    pos, box = bcc_lattice(2, 2, 1, 3.1652)
    pos = perturb(pos, 0.05, seed=2)
    b = brute_neighbors(pos, box, 3.0, max_nbors=60)
    d = cell_neighbors_device(pos, box, 3.0, max_nbors=60)
    assert (b[1].sum(1) == d[1].sum(1)).all()
    assert _pair_sets(*b[:2]) == _pair_sets(*d[:2])


def test_device_skin_build_and_shift_contract():
    """Build at rcut+skin == brute at rcut+skin; shifts reconstruct disp."""
    pos, box = paper_box(natoms=250)
    pos = perturb(pos, 0.08, seed=3)
    d = cell_neighbors_device(pos, box, 4.0, max_nbors=60, skin=0.7)
    b = brute_neighbors(pos, box, 4.7, max_nbors=60)
    assert _pair_sets(*b[:2]) == _pair_sets(*d[:2])
    nbr_idx, mask, disp, shifts = d
    recon = pos[nbr_idx] + shifts - pos[:, None, :]
    np.testing.assert_allclose(recon[mask], disp[mask], atol=1e-12)
    # masked slots carry zero shifts (padding stays inert)
    assert (shifts[~mask] == 0).all()


def test_device_unwrapped_positions_keep_pairs_and_shifts():
    """Atoms that drifted whole boxes away (the MD loop's raw positions)
    keep the wrapped build's pairs, slots and displacements; only the
    shifts absorb the drift."""
    pos, box = paper_box(natoms=250)
    pos = perturb(pos, 0.08, seed=4)
    drift = np.random.default_rng(4).integers(-3, 4, size=pos.shape) * box
    w = cell_neighbors_device(pos, box, 4.0, max_nbors=60, skin=0.7)
    u = cell_neighbors_device(pos + drift, box, 4.0, max_nbors=60, skin=0.7)
    np.testing.assert_array_equal(w[0], u[0])
    np.testing.assert_array_equal(w[1], u[1])
    np.testing.assert_allclose(u[2], w[2], atol=1e-9)
    assert (u[3][~u[1]] == 0).all()


def test_device_overflow_flags():
    """Capacity violations surface as the host builders' exceptions, driven
    by the device-side flags rather than in-trace raises."""
    pos, box = paper_box(natoms=250)
    with pytest.raises(NeighborOverflowError, match='overflow'):
        cell_neighbors_device(pos, box, 4.7, max_nbors=10)
    with pytest.raises(CellOverflowError, match='cell list overflow'):
        cell_neighbors_device(pos, box, 4.0, max_nbors=40, cell_cap=2)
    # exactly-full capacities are fine
    nbr_idx, mask, _, _ = cell_neighbors_device(pos, box, 4.7, max_nbors=26)
    assert mask.sum(1).max() == 26


def test_device_loop_matches_exact_rebuild():
    """Skin-trigger correctness: with rebuilds actually firing, the device
    loop reproduces the rebuild-every-step reference to f64 round-off
    (the per-step rcut hard cut makes both force sequences exact)."""
    cfg = SnapConfig(twojmax=4, rcut=4.7)
    rng = np.random.default_rng(2)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff) * 5e-3)
    pos, box = paper_box(natoms=54)
    pos = perturb(pos, 0.03, seed=7)
    outs = {}
    caches = {}
    for name, loop, kwa in (('device', 'device', dict(skin=0.05)),
                            ('exact', 'scan', dict(rebuild_every=1))):
        state = MDState(pos=pos.copy(),
                        vel=init_velocities(len(pos), 2000.0, seed=8),
                        box=box)
        caches[name] = {}
        _, thermo = run_nve(cfg, beta, 0.0, state, n_steps=10, dt=0.002,
                            log_every=2, loop=loop, fn_cache=caches[name],
                            **kwa)
        outs[name] = np.array([[t['T'], t['pe'], t['etot']] for t in thermo])
    assert caches['device']['device_rebuilds'] > 0   # the trigger fired
    np.testing.assert_allclose(outs['device'], outs['exact'],
                               rtol=1e-9, atol=1e-9)


def test_device_loop_zero_host_transfers_large_n():
    """N >= 2048 entirely on device: every chunk between logging
    boundaries reuses ONE jitted computation (trace-count assertion), so
    there is no host control plane — the host only reads the stacked
    (PE, KE) rows and the overflow flags."""
    cfg = SnapConfig(twojmax=2, rcut=3.0)
    rng = np.random.default_rng(0)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff) * 5e-3)
    pos, box = paper_box(natoms=2662)
    assert len(pos) >= 2048
    pos = perturb(pos, 0.02, seed=1)
    state = MDState(pos=pos.copy(),
                    vel=init_velocities(len(pos), 300.0, seed=2), box=box)
    cache = {}
    _, thermo = run_nve(cfg, beta, 0.0, state, n_steps=4, dt=0.0005,
                        log_every=2, loop='device', skin=0.5, max_nbors=16,
                        fn_cache=cache)
    # 2 chunks of 2 steps ran, but the chunk traced exactly once
    assert cache['device_trace_count']['traces'] == 1
    e = [t['etot'] for t in thermo]
    assert abs(e[-1] - e[0]) < 1e-6 * max(abs(e[0]), 1.0)


def test_device_loop_records_host_spans():
    """The device loop's host phases land in the span ring: one md.run
    around the seed and two chunks, each chunk holding its dispatch, its
    wait on the flags and its log read-back, in that order."""
    from repro.runtime import trace
    cfg = SnapConfig(twojmax=2, rcut=4.7)
    rng = np.random.default_rng(3)
    beta = jnp.asarray(rng.normal(size=cfg.ncoeff) * 5e-3)
    pos, box = paper_box(natoms=54)
    state = MDState(pos=perturb(pos, 0.03, seed=4),
                    vel=init_velocities(len(pos), 300.0, seed=5), box=box)
    trace.reset()
    run_nve(cfg, beta, 0.0, state, n_steps=4, dt=0.0005, log_every=2,
            loop='device', impl='adjoint', skin=0.05)
    spans = sorted(trace.snapshot(), key=lambda s: s[1])
    runs = [s for s in spans if s[0] == 'md.run']
    assert len(runs) == 1 and runs[0][3] is None
    r0, r1 = runs[0][1], runs[0][1] + runs[0][2]

    def within(s, lo, hi):
        return lo <= s[1] and s[1] + s[2] <= hi

    assert [s[0] for s in spans if s[3] == 'md.run'] == [
        'md.seed', 'md.chunk', 'md.chunk']
    chunks = [s for s in spans if s[0] == 'md.chunk']
    for c in chunks:
        assert within(c, r0, r1)
        inner = [s for s in spans if s[3] == 'md.chunk'
                 and within(s, c[1], c[1] + c[2])]
        assert [s[0] for s in inner] == ['md.dispatch', 'md.wait', 'md.log']


def test_device_cache_rejects_mismatched_grid():
    """fn_cache reuse across a different box geometry must raise, not
    silently reuse a CellGrid whose stencil no longer covers rcut+skin."""
    cfg = SnapConfig(twojmax=2, rcut=3.0)
    beta = jnp.zeros(cfg.ncoeff)
    cache = {}
    for natoms, should_raise in ((250, False), (54, True)):
        pos, box = paper_box(natoms=natoms)
        state = MDState(pos=perturb(pos, 0.02, seed=1),
                        vel=init_velocities(len(pos), 100.0, seed=2),
                        box=box)
        if should_raise:
            with pytest.raises(ValueError, match='device grid'):
                run_nve(cfg, beta, 0.0, state, n_steps=1, loop='device',
                        skin=0.4, max_nbors=16, fn_cache=cache)
        else:
            run_nve(cfg, beta, 0.0, state, n_steps=1, loop='device',
                    skin=0.4, max_nbors=16, fn_cache=cache)


def test_make_grid_static_hashable():
    """CellGrid must be hashable (jit static arg) and degrade to >= 1 bin."""
    g = make_grid(np.array([2.0, 9.0, 40.0]), rcut=3.0, skin=1.0)
    assert g.nbins == (1, 2, 10)
    assert hash(g) == hash(make_grid(np.array([2.0, 9.0, 40.0]), 3.0, 1.0))
    assert len(g.stencil) == 1 * 2 * 3   # deduplicated per-axis offsets
