"""Driver: NVE molecular dynamics through the device loop, of one or more
elements, on one atom domain held by one chip or sharded over several.

The configuration gives the box (``natoms``) and its elements: a
``species`` table (element, radius, weight, mass) with ``snap.rcutfac``,
or else one element of mass ``mass`` at ``snap.rcut``.  The mix file
gives what ``md_nve`` reads (``displacement_A``, ``temperature_K``,
``dt_ps``, ``log_every``, ``beta_seed``, ``sample_block``,
``integrator_atoms``, ``trace_seconds``) and, where they apply:

- ``shards``: atom shards of the force pipeline (``run_nve(shards=)``,
  one per chip), 1 when absent;
- ``fractions`` and ``species_seed``: the share of the sites of each
  element, placed by a permutation drawn from ``species_seed``.

Set-up runs one chunk to compile and times one to size the window, as
``md_nve`` does, but compiles over two chunks when sharded, and with
more than one element times ``TIMED_CHUNKS`` chunks and sizes the window
from the fastest.

As in ``md_nve``, what the program compiles into the chunk is fixed by
the mix and not drawn from ``--seed``: the coefficients (one row per
element, from ``beta_seed``), the elements of the sites and so the
masses (``species_seed``), and the cell capacity.  The seed draws the
displacements and the velocities (by each atom's mass).  With one
element and one shard every input, call and comparison is ``md_nve``'s.

With more than one element the driver needs the program's species path
(``SnapConfig``'s element table and ``run_nve(species=)``); a program
without it is refused at once, before anything compiles.

``correct`` follows ``md_nve.check``.  With more than one element the
forces at the window's last chunk boundary are compared for one atom of
each element drawn from the seed in every ``sample_block`` consecutive
atoms, and the last chunk is integrated again with the reference's
velocity Verlet for ``integrator_atoms`` atoms of each element, against
``reference_species`` (float64, per-pair cutoffs and weights, per-element
coefficients and masses).
"""

from __future__ import annotations

import time
from pathlib import Path

import numpy as np

import compare
import harness
import inputs
import reference_species

md_nve = harness.load_module(Path(__file__).with_name('md_nve.py'))

SKIN = md_nve.SKIN
# The window's length in steps comes from a timed chunk.  Where light
# atoms rebuild the lists every dozen steps, one timed chunk may hold a
# rebuild and give a window a chunk shorter; timing on until a chunk holds
# none would start the window at a step, and so at a temperature and a
# rebuild cadence, that differs from seed to seed.  So with more than one
# element set-up always times this many chunks, and the fastest (one
# without a rebuild) sizes the window, which starts at the same step in
# every run.  One element (W) first rebuilds well after set-up.
TIMED_CHUNKS = 4
release = md_nve.release
end_to_end = md_nve.end_to_end


class State(md_nve.State):
    species: np.ndarray = None
    mass: object = None
    shards: int = 1
    multi: bool = False


def elements(config: dict) -> list:
    """The configuration's element table, or its one element."""
    if 'species' in config:
        return config['species']
    return [dict(element=config.get('element', 'X'),
                 mass=float(config['mass']))]


def require_species_api():
    """The program's species path, or a refusal before any compile."""
    from repro.core.snap import SnapConfig
    from repro.md.integrate import run_nve
    import inspect
    if not (hasattr(SnapConfig, 'species_path')
            and 'species' in inspect.signature(run_nve).parameters):
        raise SystemExit('bench: the configuration has more than one '
                         'element, and the program has no multi-element '
                         'SNAP (SnapConfig element table, run_nve '
                         'species=); no result')


def snap_config(config: dict):
    if 'species' not in config:
        return inputs.snap_config(config)
    from repro.core.snap import SnapConfig
    s = config['snap']
    return SnapConfig(twojmax=int(s['twojmax']), rcutfac=float(s['rcutfac']),
                      radii=tuple(e['radius'] for e in config['species']),
                      weights=tuple(e['weight'] for e in config['species']),
                      rfac0=float(s['rfac0']), rmin0=float(s['rmin0']),
                      switch_flag=bool(s['switch_flag']),
                      bzero_flag=bool(s['bzero_flag']),
                      wself=float(s['wself']))


def species_of(run, natoms: int) -> np.ndarray:
    """Element index of every site: ``round(fraction * natoms)`` sites of
    each element after the first, placed by a permutation drawn from the
    mix's ``species_seed``; the first element takes the rest."""
    table = elements(run.config)
    species = np.zeros(natoms, np.int32)
    if len(table) == 1:
        return species
    frac = run.traffic['fractions']
    order = inputs.stream(int(run.traffic['species_seed']),
                          'species').permutation(natoms)
    at = 0
    for code, e in enumerate(table[1:], start=1):
        n = int(round(float(frac[e['element']]) * natoms))
        species[order[at:at + n]] = code
        at += n
    return species


def velocities(mass: np.ndarray, temp: float, rng):
    """Maxwell-Boltzmann velocities (Å/ps) by each atom's mass, with zero
    total momentum."""
    sigma = np.sqrt(inputs.KB * temp / (mass / inputs.ACC_CONV))
    v = rng.normal(size=(len(mass), 3)) * sigma[:, None]
    return v - (mass[:, None] * v).sum(0) / mass.sum()


def _run(st, run, n_steps, tap=None):
    from repro.md.integrate import run_nve
    mix = run.traffic
    kw = dict(species=st.species) if st.multi else {}
    return run_nve(st.cfg, st.beta, 0.0, st.state, n_steps,
                   dt=float(mix['dt_ps']), mass=st.mass, impl='kernel',
                   loop='device', log_every=int(mix['log_every']),
                   fn_cache=st.cache, skin=SKIN, cell_cap=st.cell_cap,
                   force_kwargs=st.kwargs, fault_hook=tap,
                   shards=st.shards, **kw)


def setup(run, force_kwargs=None):
    from repro.md.cell_list import auto_cell_cap
    from repro.md.integrate import MDState
    mix, cfg = run.traffic, run.config
    table = elements(cfg)
    multi = len(table) > 1
    if multi:
        require_species_api()
    lattice, box = inputs.lattice(cfg)
    pos = inputs.displaced(lattice, box, float(mix['displacement_A']),
                           inputs.stream(run.seed, 'displacement'))
    species = species_of(run, len(pos))
    brng = inputs.stream(int(mix['beta_seed']), 'beta')
    vrng = inputs.stream(run.seed, 'velocity')
    temp = float(mix['temperature_K'])
    if multi:
        mass = np.array([e['mass'] for e in table], np.float64)[species]
        beta = np.stack([inputs.beta(cfg, brng) for _ in table])
        vel = velocities(mass, temp, vrng)
    else:
        mass = float(cfg['mass'])
        beta = inputs.beta(cfg, brng)
        vel = inputs.velocities(len(pos), temp, mass, vrng)
    st = State(cfg=snap_config(cfg), beta=beta, box=box, natoms=len(pos),
               kwargs=dict(force_kwargs or {}))
    st.species, st.mass, st.multi = species, mass, multi
    st.shards = int(mix.get('shards', 1))
    st.cell_cap = auto_cell_cap(lattice, box, st.cfg.rcut + SKIN)
    st.state = MDState(pos=pos, vel=vel, box=box)
    chunk = int(mix['log_every'])
    t0 = time.perf_counter()
    # sharded, a chunk's outputs carry the mesh's sharding, and the chunk
    # program compiles once more for them: the first call runs two chunks
    # so that this compile, too, falls in set-up and not in the window
    _run(st, run, chunk * (2 if st.shards > 1 else 1))
    run.counters['setup_first_call_s'] = time.perf_counter() - t0
    times = []
    for _ in range(TIMED_CHUNKS if multi else 1):
        t0 = time.perf_counter()
        _run(st, run, chunk)
        times.append(time.perf_counter() - t0)
    st.chunk_s = min(times)
    run.counters['setup_chunks'] = len(times)
    return st


def measure(st, run, seconds):
    chunk = int(run.traffic['log_every'])
    n = chunk * max(1, int(round(seconds / st.chunk_s)))
    tap = md_nve.Tap()
    with run.span('window'):
        t0 = time.perf_counter()
        with run.span('run_nve'):
            _run(st, run, n, tap)
        st.wall_s = time.perf_counter() - t0
    st.steps = n
    with run.span('result_readback'):
        st.carry = {k: np.asarray(tap.carry[k]) for k in
                    ('pos', 'vel', 'f', 'nbr_idx', 'shifts', 'mask')}
    st.final_pos = np.asarray(st.state.pos)
    pos, ni, sh, m = (st.carry[k] for k in ('pos', 'nbr_idx', 'shifts',
                                            'mask'))
    d = pos[ni] + sh - pos[:, None, :]
    if st.multi:
        rc = reference_species.table(run.config)[0][
            st.species[:, None], st.species[ni]]
        run.counters['species_pairs'] = st.cache.get('species_pairs')
    else:
        rc = float(run.snap['rcut'])
    npairs = int((m & (np.sum(d * d, -1) < rc * rc)).sum())
    rebuilds = int(st.cache.get('device_rebuilds', 0))
    run.counters.update(steps=n, atoms=st.natoms, npairs=npairs,
                        force_evals=n + 1, atom_steps=n * st.natoms,
                        wall_s=st.wall_s, rebuilds=rebuilds,
                        twojmax=int(run.snap['twojmax']),
                        padded_nbors=int(m.shape[1]), shards=st.shards)


def sampled_atoms(run, species: np.ndarray) -> np.ndarray:
    """One atom of each element drawn from the seed in every block of
    ``sample_block`` consecutive atoms (a kernel's lane tile at 128)."""
    rng = inputs.stream(run.seed, 'sample')
    block = int(run.traffic['sample_block'])
    out = []
    for lo in range(0, len(species), block):
        sp = species[lo:lo + block]
        for e in range(len(elements(run.config))):
            where = np.flatnonzero(sp == e)
            if len(where):
                out.append(lo + where[int(rng.random() * len(where))])
    return np.array(out, np.int64)


def check(st, run):
    """``md_nve.check`` with one element; with more, forces of the sampled
    atoms of every element at the last chunk boundary, and the positions
    the last chunk produced for ``integrator_atoms`` atoms of each
    element, against ``reference_species``."""
    if not st.multi:
        return md_nve.check(st, run)
    c, mix, lim = st.carry, run.traffic, run.workload['limits']
    atoms = sampled_atoms(run, st.species)
    _, f_ref = reference_species.forces_on(
        run.config, st.beta, 0.0, c['pos'], st.box, st.species, atoms)
    out = [('force_rel_err', compare.rel_err(c['f'][atoms], f_ref),
            lim['force_rel_err'])]
    rng = inputs.stream(run.seed, 'integrator')
    k = int(mix['integrator_atoms'])
    chosen = np.sort(np.concatenate([
        rng.choice(np.flatnonzero(st.species == e), k, replace=False)
        for e in range(len(elements(run.config)))]))
    x_ref = reference_species.verlet_local(
        run.config, st.beta, 0.0, c['pos'], c['vel'], st.box, st.species,
        chosen, int(mix['log_every']), float(mix['dt_ps']),
        inputs.ACC_CONV / st.mass)
    moved = np.abs(x_ref - c['pos'][chosen]).max()
    err = float(np.abs(st.final_pos[chosen] - x_ref).max() / moved)
    out.append(('integrator_rel_err', err, lim['integrator_rel_err']))
    return out, st.steps, 0
