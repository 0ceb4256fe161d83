"""Driver: NVE molecular dynamics through the device loop.

The configuration gives the box (``natoms``); the mix file gives
``displacement_A``, ``temperature_K``, ``dt_ps``, ``log_every``,
``beta_seed``, ``sample_block``, ``integrator_atoms`` and
``trace_seconds``.

The program compiles the coefficients into the chunk program as
constants, and the cell list's capacity into its shapes.  So neither is
drawn from ``--seed``: the coefficients come from the mix's
``beta_seed``, as a fitted potential is fixed across runs, and the
capacity is the device loop's own sizing of the perfect lattice.  A
second run of the cell then finds every program in the compilation
cache.  The seed
draws the displacements and velocities.

Set-up builds the seeded box and velocities and drives
``run_nve(loop='device', impl='kernel')`` from them through two calls of
``log_every`` steps (the first compiles, the second times a chunk); the
window is one more ``run_nve`` call on the same state and cache, of a
whole number of chunks sized to last about ``--seconds``.  Each ``run_nve`` call also evaluates the force once at
its start, so a window of n steps holds n + 1 force evaluations.

``run_nve``'s ``fault_hook`` is called at every chunk boundary with the
committed device carry; the driver's hook returns it unchanged and keeps
it.  So ``correct`` compares the forces that the trajectory itself used
(the chunk scan's kernel pipeline on its cell-list neighbours, at the
positions the integrator produced) at the last boundary of the window,
for one atom drawn from the seed in every ``sample_block`` consecutive
atoms, with the float64 reference at those positions.  It also integrates the last chunk again, from the carry at
that boundary, with the reference's velocity Verlet over the sampled
atoms' neighbourhoods (``reference.verlet_local``), and compares the
positions the window ended with.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

import compare
import inputs
import reference

SKIN = 1.0      # Å, run_nve's default Verlet skin


@dataclass
class State:
    state: object = None
    cfg: object = None
    beta: np.ndarray = None
    box: np.ndarray = None
    cache: dict = field(default_factory=dict)
    kwargs: dict = field(default_factory=dict)
    cell_cap: int = 0
    chunk_s: float = 0.0
    steps: int = 0
    wall_s: float = 0.0
    carry: dict = None
    final_pos: np.ndarray = None
    natoms: int = 0


class Tap:
    """``fault_hook`` that changes nothing and keeps the last carry."""

    def __init__(self):
        self.carry = None

    def __call__(self, step, carry, grid):
        self.carry = carry
        return carry


def _run(st, run, n_steps, tap=None):
    from repro.md.integrate import run_nve
    mix = run.traffic
    return run_nve(st.cfg, st.beta, 0.0, st.state, n_steps,
                   dt=float(mix['dt_ps']), mass=float(run.config['mass']),
                   impl='kernel', loop='device',
                   log_every=int(mix['log_every']), fn_cache=st.cache,
                   skin=SKIN, cell_cap=st.cell_cap, force_kwargs=st.kwargs,
                   fault_hook=tap)


def setup(run, force_kwargs=None):
    from repro.md.cell_list import auto_cell_cap
    from repro.md.integrate import MDState
    mix, cfg = run.traffic, run.config
    lattice, box = inputs.lattice(cfg)
    pos = inputs.displaced(lattice, box, float(mix['displacement_A']),
                           inputs.stream(run.seed, 'displacement'))
    vel = inputs.velocities(len(pos), float(mix['temperature_K']),
                            float(cfg['mass']),
                            inputs.stream(run.seed, 'velocity'))
    st = State(cfg=inputs.snap_config(cfg),
               beta=inputs.beta(cfg, inputs.stream(int(mix['beta_seed']),
                                                   'beta')),
               box=box, natoms=len(pos), kwargs=dict(force_kwargs or {}))
    st.cell_cap = auto_cell_cap(lattice, box, st.cfg.rcut + SKIN)
    st.state = MDState(pos=pos, vel=vel, box=box)
    chunk = int(mix['log_every'])
    t0 = time.perf_counter()
    _run(st, run, chunk)
    run.counters['setup_first_call_s'] = time.perf_counter() - t0
    t0 = time.perf_counter()
    _run(st, run, chunk)
    st.chunk_s = time.perf_counter() - t0
    return st


def measure(st, run, seconds):
    chunk = int(run.traffic['log_every'])
    n = chunk * max(1, int(round(seconds / st.chunk_s)))
    tap = Tap()
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
    rc = float(run.snap['rcut'])
    npairs = int((m & (np.sum(d * d, -1) < rc * rc)).sum())
    rebuilds = int(st.cache.get('device_rebuilds', 0))
    run.counters.update(steps=n, atoms=st.natoms, npairs=npairs,
                        force_evals=n + 1, atom_steps=n * st.natoms,
                        wall_s=st.wall_s, rebuilds=rebuilds,
                        twojmax=int(run.snap['twojmax']),
                        padded_nbors=int(m.shape[1]))


def release(st):
    st.cache = {}
    st.state = None


def end_to_end(st, run):
    return dict(katom_steps_per_s=(st.steps * st.natoms / st.wall_s / 1e3,
                                   'katom-steps/s'))


def check(st, run):
    """Forces at the last chunk boundary of the window, and the positions
    that the last chunk's integrator produced from that boundary, for
    atoms drawn from the seed, against the float64 reference."""
    c, mix = st.carry, run.traffic
    out = compare.atoms_against_reference(run, c['pos'], st.box, st.beta,
                                          c['f'])
    rng = inputs.stream(run.seed, 'integrator')
    atoms = np.sort(rng.choice(st.natoms, int(mix['integrator_atoms']),
                               replace=False))
    x_ref = reference.verlet_local(
        run.snap, st.beta, 0.0, c['pos'], c['vel'], st.box, atoms,
        int(mix['log_every']), float(mix['dt_ps']),
        inputs.ACC_CONV / float(run.config['mass']))
    moved = np.abs(x_ref - c['pos'][atoms]).max()
    err = float(np.abs(st.final_pos[atoms] - x_ref).max() / moved)
    out.append(('integrator_rel_err', err,
                run.workload['limits']['integrator_rel_err']))
    return out, st.steps, 0
