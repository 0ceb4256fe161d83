"""Driver: repeated force calls on one fixed configuration.

The configuration gives the box (``natoms``); the mix file gives
``displacement_A``, ``nnbor`` (padded neighbour width), ``sample_block``
and ``trace_seconds``.  Each call is the jitted
``energy_forces(impl='kernel')`` on the same inputs, blocked until ready;
the window is a number of calls sized from the warm-up so that it lasts
about ``--seconds``.  ``correct`` compares the last timed call's forces
on one atom drawn from the seed in every ``sample_block`` consecutive
atoms, and its per-atom energies on those atoms' neighbourhoods, with
the float64 reference (``compare.atoms_against_reference``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass

import numpy as np

import compare
import inputs
import reference


@dataclass
class State:
    fn: object = None
    args: tuple = ()
    pos: np.ndarray = None
    box: np.ndarray = None
    beta: np.ndarray = None
    npairs: int = 0
    call_s: float = 0.0
    out: tuple = None
    calls: int = 0
    wall_s: float = 0.0
    natoms: int = 0


def problem(run):
    mix, cfg = run.traffic, run.config
    pos, box = inputs.lattice(cfg)
    pos = inputs.displaced(pos, box, float(mix['displacement_A']),
                           inputs.stream(run.seed, 'displacement'))
    beta = inputs.beta(cfg, inputs.stream(run.seed, 'beta'))
    idx, disp, mask = reference.neighbours(pos, box, np.arange(len(pos)),
                                           float(run.snap['rcut']))
    k = int(mix['nnbor'])
    if idx.shape[1] > k:
        raise RuntimeError(f'an atom has {idx.shape[1]} neighbours, more '
                           f'than the mix width {k}')
    pad = [(0, 0), (0, k - idx.shape[1])]
    idx = np.pad(idx, pad)
    mask = np.pad(mask, pad)
    disp = np.pad(disp, pad + [(0, 0)])
    return pos, box, beta, idx, disp, mask


def setup(run, force_kwargs=None):
    import jax
    import jax.numpy as jnp

    from repro.core.snap import energy_forces

    pos, box, beta, idx, disp, mask = problem(run)
    cfg = inputs.snap_config(run.config)
    kw = dict(force_kwargs or {})
    f32 = np.float32
    args = (jnp.asarray(beta, f32), jnp.asarray(disp[..., 0], f32),
            jnp.asarray(disp[..., 1], f32), jnp.asarray(disp[..., 2], f32),
            jnp.asarray(idx, jnp.int32), jnp.asarray(mask))
    fn = jax.jit(lambda b, dx, dy, dz, ni, m: energy_forces(
        cfg, b, 0.0, dx, dy, dz, ni, m, impl='kernel', **kw))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    run.counters['setup_first_call_s'] = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    st = State(fn=fn, args=args, pos=pos, box=box, beta=beta,
               npairs=int(mask.sum()), call_s=time.perf_counter() - t0,
               natoms=len(pos))
    return st


def measure(st, run, seconds):
    import jax
    n = max(1, int(round(seconds / st.call_s)))
    with run.span('window'):
        t0 = time.perf_counter()
        for _ in range(n):
            with run.span('force_call'):
                out = jax.block_until_ready(st.fn(*st.args))
        st.wall_s = time.perf_counter() - t0
    st.calls = n
    with run.span('result_readback'):
        st.out = tuple(np.asarray(x, np.float64) for x in out)
    run.counters.update(calls=n, atoms=st.natoms, npairs=st.npairs,
                        force_evals=n, atom_steps=n * st.natoms,
                        wall_s=st.wall_s,
                        twojmax=int(run.snap['twojmax']))


def release(st):
    st.fn = None
    st.args = ()


def end_to_end(st, run):
    return dict(katom_steps_per_s=(st.calls * st.natoms / st.wall_s / 1e3,
                                   'katom-steps/s'))


def check(st, run):
    _, e_atom, forces = st.out
    return compare.atoms_against_reference(
        run, st.pos, st.box, st.beta, forces, e_atom), st.calls, 0

