"""Velocity-Verlet NVE integration driving the SNAP force pipelines.

Three loop drivers, fastest first:

- ``loop='device'``: the fully on-device engine — neighbor rebuilds run as
  traced JAX ops (:mod:`repro.md.cell_list`) *inside* the jitted step scan,
  triggered by a half-skin displacement check (``lax.cond``), so there is no
  host control plane at all: the host only reads back stacked (PE, KE) rows
  and overflow flags at logging boundaries.  Lists are built at
  ``rcut + skin`` and hard-cut at ``rcut`` per step, which (a) makes forces
  exact regardless of when the last rebuild happened and (b) keeps the
  Cayley-Klein ``theta0 = pi`` singularity just beyond rcut out of the
  kernels.
- ``loop='scan'``: the LAMMPS-shaped A/B driver — neighbor lists rebuild on
  the host every ``rebuild_every`` steps (fixed-shape padded lists), the
  inner velocity-Verlet segment runs as ONE jitted ``jax.lax.scan``.
- ``loop='host'``: the legacy per-step driver (one jitted force call per
  step) for A/B benchmarking (see benchmarks/b_md_grind.py).

Thermodynamic output (temperature, PE, virial pressure) reproduces the
verification methodology of the paper's Sec. VI ("comparing the
thermodynamic output of the new version to that of the baseline").
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import record_trace
from repro.core.snap import SnapConfig, energy_forces, species_pairs
from repro.runtime.trace import span
from .cell_list import (FLAG_DRIFT, FLAG_ESCAPE, FLAG_NAN_FORCE,
                        FLAG_NAN_STATE, N_FLAGS, auto_cell_cap,
                        check_flags, device_neighbors, jitted_build,
                        make_grid)
from .neighbor import brute_neighbors

KB = 8.617333262e-5      # eV/K
# mass in LAMMPS 'metal' units: grams/mole; time ps; conversion for
# a = F/m: 1 eV/(A*g/mol) = 9648.53 A/ps^2
ACC_CONV = 9648.533212331
W_MASS = 183.84


@dataclass
class MDState:
    pos: np.ndarray
    vel: np.ndarray
    box: np.ndarray
    step: int = 0


def init_velocities(n, temp, mass=W_MASS, seed=0):
    """Maxwell-Boltzmann velocities; ``mass`` a scalar or one per atom
    (zero total momentum either way)."""
    rng = np.random.default_rng(seed)
    m = np.asarray(mass, np.float64)
    sigma = np.sqrt(KB * temp / (m / ACC_CONV))
    if m.ndim:
        sigma = sigma[:, None]
    v = rng.normal(size=(n, 3)) * sigma
    if m.ndim:
        return v - (m[:, None] * v).sum(0) / m.sum()
    return v - v.mean(0)


def temperature(vel, mass=W_MASS):
    m = np.asarray(mass, np.float64)
    m = m[:, None] if m.ndim else m
    ke = 0.5 * float(np.sum(m * vel * vel)) / ACC_CONV
    return 2.0 * ke / (3.0 * len(vel) * KB), ke


def _mass_terms(mass):
    """(acc_scale, kinetic(vel)) of the integrators: ``mass`` a scalar,
    or one per atom (a [N, 1] constant in the compiled step)."""
    if np.ndim(mass) == 0:
        def kinetic(vel):
            return (0.5 * mass / ACC_CONV) * jnp.sum(vel * vel)
        return ACC_CONV / mass, kinetic
    m = jnp.asarray(np.asarray(mass, np.float64)[:, None])

    def kinetic(vel):
        return (0.5 / ACC_CONV) * jnp.sum(m * vel * vel)
    return ACC_CONV / m, kinetic


def species_pair_counts(cfg: SnapConfig, species, pos, nbr_idx, shifts,
                        mask):
    """[nelements, nelements] counts of the ordered pairs (i, j) inside
    their own cutoff ``rcut_ij``, by (element of i, element of j)."""
    sp_i, _, rc = species_pairs(cfg, species, nbr_idx)
    disp = pos[nbr_idx] + shifts - pos[:, None, :]
    inside = mask & (jnp.sum(disp * disp, -1) < rc * rc)
    ne = cfg.nelements
    code = sp_i[:, None] * ne + jnp.asarray(species, jnp.int32)[nbr_idx]
    return jnp.zeros(ne * ne, jnp.int32).at[code.reshape(-1)].add(
        inside.reshape(-1).astype(jnp.int32)).reshape(ne, ne)


def make_force_fn(cfg: SnapConfig, beta, beta0, impl='adjoint', **kw):
    @partial(jax.jit, static_argnames=())
    def force_fn(dx, dy, dz, nbr_idx, mask):
        e, e_atom, f = energy_forces(cfg, beta, beta0, dx, dy, dz,
                                     nbr_idx, mask, impl=impl, **kw)
        return e, f
    return force_fn


def make_segment_fn(cfg: SnapConfig, beta, beta0, dt, mass,
                    impl='adjoint', n_sub: int = 10, **kw):
    """One jitted scan over ``n_sub`` velocity-Verlet steps.

    Carry = (pos, vel, f) on device; per-step outputs (pe, ke) come back
    stacked so logging needs no extra device round trips.  Displacements are
    recomputed on device from the rebuild-time topology + image shifts (the
    same contract as the autodiff oracle's ``make_energy_fn``).
    """
    acc_scale, kinetic = _mass_terms(mass)

    @jax.jit
    def segment(pos, vel, f, nbr_idx, shifts, mask):
        def step(carry, _):
            pos, vel, f = carry
            vel = vel + (0.5 * dt * acc_scale) * f
            pos = pos + dt * vel
            disp = pos[nbr_idx] + shifts - pos[:, None, :]
            e, _, f_new = energy_forces(
                cfg, beta, beta0, disp[..., 0], disp[..., 1], disp[..., 2],
                nbr_idx, mask, impl=impl, **kw)
            vel = vel + (0.5 * dt * acc_scale) * f_new
            ke = kinetic(vel)
            return (pos, vel, f_new), (e, ke)

        (pos, vel, f), (pe, ke) = jax.lax.scan(
            step, (pos, vel, f), None, length=n_sub)
        return pos, vel, f, pe, ke
    return segment


def make_device_chunk_fn(cfg: SnapConfig, beta, beta0, dt, mass, grid,
                         impl='adjoint', n_sub: int = 10, force_fn=None,
                         trace_counter=None, policy=None, **kw):
    """One jitted scan over ``n_sub`` steps with the rebuild folded in.

    Carry = (pos, vel, f, nbr_idx, shifts, mask, pos_ref, flags), all on
    device.  Each step: half-kick, drift, then a ``lax.cond`` that rebuilds
    the cell list at the *current* positions when any atom has moved more
    than skin/2 since ``pos_ref`` (the positions of the last build) —
    otherwise the carried topology is provably still a superset of the
    exact rcut pair set.  The force pipeline then sees a per-step hard cut
    ``mask & (r^2 < rcut^2)``, so forces are identical to a
    rebuild-every-step reference.

    ``flags`` is the ``[N_FLAGS]`` int32 health lattice: slots 0-1 carry
    the running maxima of neighbor/cell occupancy from the in-scan
    rebuilds; with a :class:`~repro.md.resilience.RecoveryPolicy` the
    step body additionally latches sticky non-finite-force/state,
    atom-escape, and energy-drift indicators (slots 2-5).  Everything is
    on-device reductions folded into the scan carry — the host sees the
    vector in the same per-chunk readback as the thermo rows, so the
    guards add no synchronization points.  ``e_ref`` is the watchdog's
    reference total energy (traced scalar; unused when the policy has no
    ``drift_tol``).

    The step's XLA ops carry the named scopes ``md.verlet`` (half-kicks
    and drift), ``md.cell_list`` (rebuild test and rebuild), ``md.pairs``
    (pair displacements and the hard cut) and ``md.flags`` (guards),
    which a profile shows as each op's ``tf_op``.

    force_fn: optional override for the force evaluation, e.g. an
    atom-sharded ``shard_map`` pipeline from
    :func:`repro.kernels.ops.make_sharded_force_fn`; signature
    ``(dx, dy, dz, nbr_idx, mask) -> (e, e_atom, f)``.
    """
    acc_scale, kinetic = _mass_terms(mass)
    half_skin2 = (0.5 * grid.skin) ** 2
    rc2 = cfg.rcut * cfg.rcut
    counter = trace_counter if trace_counter is not None else {}
    guards = policy is not None
    escape_factor = getattr(policy, 'escape_factor', None)
    drift_tol = getattr(policy, 'drift_tol', None)

    def eval_force(disp, nbr_idx, mask_t):
        if force_fn is not None:
            e, _, f = force_fn(disp[..., 0], disp[..., 1], disp[..., 2],
                               nbr_idx, mask_t)
        else:
            e, _, f = energy_forces(cfg, beta, beta0, disp[..., 0],
                                    disp[..., 1], disp[..., 2], nbr_idx,
                                    mask_t, impl=impl, **kw)
        return e, f

    @jax.jit
    def chunk(pos, vel, f, box, nbr_idx, shifts, mask, pos_ref, flags,
              e_ref):
        record_trace(counter)

        def step(carry, _):
            pos, vel, f, nbr_idx, shifts, mask, pos_ref, flags = carry
            with jax.named_scope('md.verlet'):
                vel = vel + (0.5 * dt * acc_scale) * f
                pos = pos + dt * vel

            def rebuild(_):
                ni, ms, sh, fl = device_neighbors(pos, box, grid)
                return ni, sh, ms, pos, flags.at[:2].max(fl), jnp.int32(1)

            def keep(_):
                return nbr_idx, shifts, mask, pos_ref, flags, jnp.int32(0)

            with jax.named_scope('md.cell_list'):
                moved2 = jnp.max(jnp.sum((pos - pos_ref) ** 2, axis=-1))
                # skin=0 degenerates to rebuild-every-step (moved2 >= 0)
                trigger = (moved2 > half_skin2) if grid.skin > 0 else (
                    moved2 >= 0.0)
                (nbr_idx, shifts, mask, pos_ref, flags,
                 rebuilt) = jax.lax.cond(trigger, rebuild, keep, None)
            with jax.named_scope('md.pairs'):
                disp = pos[nbr_idx] + shifts - pos[:, None, :]
                r2 = jnp.sum(disp * disp, axis=-1)
                mask_t = mask & (r2 < rc2)          # exact per-step cutoff
            e, f_new = eval_force(disp, nbr_idx, mask_t)
            with jax.named_scope('md.verlet'):
                vel = vel + (0.5 * dt * acc_scale) * f_new
                ke = kinetic(vel)
            if guards:
                with jax.named_scope('md.flags'):
                    # sticky health lattice: cheap O(N) reductions vs the
                    # O(N*K*ncoeff) force pipeline, merged into the carried
                    # running-max vector (no extra host syncs)
                    bad_f = ~jnp.all(jnp.isfinite(f_new))
                    bad_s = ~(jnp.all(jnp.isfinite(pos))
                              & jnp.all(jnp.isfinite(vel)))
                    esc = jnp.max(jnp.abs(pos / box - 0.5)) > escape_factor
                    health = [jnp.int32(0)] * N_FLAGS
                    health[FLAG_NAN_FORCE] = bad_f.astype(jnp.int32)
                    health[FLAG_NAN_STATE] = bad_s.astype(jnp.int32)
                    health[FLAG_ESCAPE] = esc.astype(jnp.int32)
                    if drift_tol is not None:
                        drifted = jnp.abs((e + ke) - e_ref) > drift_tol
                        health[FLAG_DRIFT] = drifted.astype(jnp.int32)
                    flags = jnp.maximum(flags, jnp.stack(health))
            carry = (pos, vel, f_new, nbr_idx, shifts, mask, pos_ref, flags)
            return carry, (e, ke, rebuilt)

        carry = (pos, vel, f, nbr_idx, shifts, mask, pos_ref, flags)
        carry, (pe, ke, rebuilt) = jax.lax.scan(step, carry, None,
                                                length=n_sub)
        (pos, vel, f, nbr_idx, shifts, mask, pos_ref, flags) = carry
        return (pos, vel, f, nbr_idx, shifts, mask, pos_ref, flags,
                pe, ke, rebuilt.sum())
    return chunk


def virial_pressure(dedr_like_forces, pos, box):
    """Rough isotropic virial from forces (diagnostic only)."""
    vol = float(np.prod(box))
    w = float(np.sum(np.asarray(dedr_like_forces) * np.asarray(pos)))
    return w / (3.0 * vol)


def run_nve(cfg: SnapConfig, beta, beta0, state: MDState, n_steps: int,
            dt: float = 0.0005, mass: float = W_MASS,
            impl: str = 'adjoint', rebuild_every: int = 10,
            max_nbors: int = 40, log_every: int = 10,
            loop: str = 'scan', force_kwargs: Dict | None = None,
            fn_cache: Dict | None = None, skin: float = 1.0,
            cell_cap: int | None = None, shards: int = 1,
            policy=None, checkpoint_dir=None, checkpoint_every: int = 0,
            restore: bool = False, fault_hook=None, species=None):
    """NVE loop; returns (state, list of thermo dicts).

    Multi-element SNAP: ``species`` is the element index of every atom
    (static for the run), ``beta``/``beta0`` are per element (see
    :mod:`repro.core.snap`), and ``mass`` may be one per atom.  Lists are
    built at the largest pair cutoff (``cfg.rcut``) plus the skin; each
    pair is cut at its own cutoff inside the force pipeline.  The device
    loop then records ``fn_cache['species_pairs']``: the pairs inside
    their cutoff by (element, element) at the last rebuild.

    loop='device' folds the neighbor rebuild into the jitted step scan (a
    half-skin displacement trigger decides rebuilds on device); the host
    only reads logging rows and overflow flags at chunk boundaries.
    loop='scan' (default) runs each inter-rebuild segment as one on-device
    ``lax.scan`` with host rebuilds; loop='host' steps on the host (one
    jitted force call per step).  All evaluate the force exactly once per
    step (plus once at step 0) — identical trajectories up to
    image-convention round-off (the device path is additionally exact at
    rcut per step thanks to its hard cut on the skin-padded lists).

    skin / cell_cap / shards apply to loop='device' only: Verlet skin
    radius (Å), static cell capacity (auto-sized from the initial
    configuration when None), and atom shards for the force pipeline (>1
    wraps the force evaluation in shard_map over `len(jax.devices())`-bound
    atom shards; natoms must divide by shards).  max_nbors keeps its
    host-path meaning (capacity of the rcut sphere); the device build
    auto-scales it to the rcut+skin shell.

    Resilience (loop='device' only — see DESIGN.md "Failure model"):
    a :class:`repro.md.resilience.RecoveryPolicy` arms the in-scan health
    guards and turns capacity overflows into regrow+re-jit+rollback and
    numeric blow-ups into rollback+dt-halving retries (bounded, typed
    errors past the budget); without a policy the first overflow raises
    at the chunk boundary exactly as before.  checkpoint_dir +
    checkpoint_every snapshot the full device carry atomically every >=
    checkpoint_every committed steps; restore=True resumes from the
    latest snapshot under checkpoint_dir (bitwise-identical continuation
    when chunk boundaries align, i.e. checkpoint_every is a multiple of
    log_every).  fault_hook (see repro.md.fault_inject) is called at
    every chunk boundary to inject deterministic faults for testing.

    force_kwargs are forwarded to the force implementation; for
    impl='kernel' this includes the half-plane pipeline knobs
    (``layout='half'|'full'``, ``y_tile``, ``mxu_dtype`` — see
    repro.kernels.ops.snap_force_pipeline).

    fn_cache: optional dict reused across calls to keep the jitted force /
    segment functions (and their compilations) alive — benchmarks pass the
    same dict to warmup and timed runs.  The cached closures bake in the
    physics parameters, so reuse is only valid for identical (cfg, beta,
    beta0, dt, mass, impl, skin, shards, force_kwargs) — enforced via a
    fingerprint.
    """
    if fn_cache is not None:
        fp = (cfg, np.asarray(beta).tobytes(), np.asarray(beta0).tobytes(),
              float(dt), np.asarray(mass, np.float64).tobytes(), impl,
              float(skin), int(shards),
              tuple(sorted((force_kwargs or {}).items())),
              None if species is None else np.asarray(species).tobytes())
        if fn_cache.setdefault('fingerprint', fp) != fp:
            raise ValueError(
                'fn_cache was built for different physics parameters '
                '(cfg/beta/dt/mass/impl/...); pass a fresh dict')
    if loop != 'device' and (policy is not None or checkpoint_dir
                             or restore or fault_hook):
        raise ValueError(
            'policy/checkpoint/restore/fault_hook are device-loop '
            "features; use loop='device'")
    if cfg.species_path:
        if species is None or len(species) != len(state.pos):
            raise ValueError('a multi-element config needs species, one '
                             'element index per atom')
        force_kwargs = dict(force_kwargs or {},
                            species=np.asarray(species, np.int32))
    if loop == 'device':
        with span('md.run'):
            return _run_nve_device(cfg, beta, beta0, state, n_steps, dt,
                                   mass, impl, max_nbors, log_every,
                                   force_kwargs, fn_cache, skin, cell_cap,
                                   shards, policy, checkpoint_dir,
                                   checkpoint_every, restore, fault_hook)
    if loop == 'scan':
        return _run_nve_scan(cfg, beta, beta0, state, n_steps, dt, mass,
                             impl, rebuild_every, max_nbors, log_every,
                             force_kwargs, fn_cache)
    if loop == 'host':
        return _run_nve_host(cfg, beta, beta0, state, n_steps, dt, mass,
                             impl, rebuild_every, max_nbors, log_every,
                             force_kwargs, fn_cache)
    raise ValueError(
        f"unknown loop {loop!r}; choose 'device', 'scan' or 'host'")


def _log_rows(thermo, seg_pe, seg_ke, first_step, base_step, n_atoms,
              n_steps, log_every):
    """Append thermo dicts for the logged steps of one scan segment."""
    for k, (pe, ke) in enumerate(zip(seg_pe, seg_ke)):
        it = first_step + k
        if it % log_every == 0 or it == n_steps - 1:
            ke = float(ke)
            T = 2.0 * ke / (3.0 * n_atoms * KB)
            thermo.append(dict(step=base_step + it + 1, T=T, ke=ke,
                               pe=float(pe), etot=float(pe) + ke))


def _run_nve_scan(cfg, beta, beta0, state, n_steps, dt, mass, impl,
                  rebuild_every, max_nbors, log_every, force_kwargs,
                  fn_cache=None):
    kw = force_kwargs or {}
    cache = fn_cache if fn_cache is not None else {}
    if 'force' not in cache:
        cache['force'] = make_force_fn(cfg, beta, beta0, impl, **kw)
    force_fn = cache['force']
    n_atoms = len(state.pos)
    segments = cache.setdefault('segments', {})   # n_sub -> jitted segment
    thermo = []
    pos = vel = f = None
    it = 0
    while it < n_steps:
        n_sub = min(rebuild_every, n_steps - it)
        # host boundary: rebuild topology at current positions
        pos_h = np.asarray(pos) if pos is not None else state.pos
        nbr_idx, mask, disp, shifts = brute_neighbors(
            pos_h, state.box, cfg.rcut, max_nbors)
        if f is None:   # first segment: seed the force carry once
            _, f = force_fn(disp[..., 0], disp[..., 1], disp[..., 2],
                            nbr_idx, mask)
            pos = jnp.asarray(pos_h)
            vel = jnp.asarray(state.vel)
        if n_sub not in segments:
            segments[n_sub] = make_segment_fn(
                cfg, beta, beta0, dt, mass, impl, n_sub, **kw)
        pos, vel, f, seg_pe, seg_ke = segments[n_sub](
            pos, vel, f, jnp.asarray(nbr_idx), jnp.asarray(shifts),
            jnp.asarray(mask))
        _log_rows(thermo, np.asarray(seg_pe), np.asarray(seg_ke), it,
                  state.step, n_atoms, n_steps, log_every)
        it += n_sub
    if pos is not None:
        state.pos = np.asarray(pos)
        state.vel = np.asarray(vel)
    state.step += n_steps
    return state, thermo


def _seed_force(cache, cfg, beta, beta0, impl, kw, force_fn, pos,
                nbr_idx, shifts, mask):
    """Force + energy at the carried positions (exact rcut cut), jitted —
    used at step 0 and after capacity regrows (same positions, wider
    topology)."""
    disp = pos[nbr_idx] + shifts - pos[:, None, :]
    mask0 = mask & (jnp.sum(disp * disp, -1) < cfg.rcut * cfg.rcut)
    if force_fn is not None:
        e, _, f = force_fn(disp[..., 0], disp[..., 1], disp[..., 2],
                           nbr_idx, mask0)
    else:
        if 'force' not in cache:
            cache['force'] = make_force_fn(cfg, beta, beta0, impl, **kw)
        e, f = cache['force'](disp[..., 0], disp[..., 1], disp[..., 2],
                              nbr_idx, mask0)
    return e, f


def _full_flags(build_flags):
    """Lift the [2] build flags into the [N_FLAGS] health lattice."""
    return jnp.zeros(N_FLAGS, jnp.int32).at[:2].set(
        jnp.asarray(build_flags, jnp.int32))


def _run_nve_device(cfg, beta, beta0, state, n_steps, dt, mass, impl,
                    max_nbors, log_every, force_kwargs, fn_cache, skin,
                    cell_cap, shards, policy=None, checkpoint_dir=None,
                    checkpoint_every=0, restore=False, fault_hook=None):
    """Fully on-device driver: rebuilds inside the jitted chunk scan.

    The host's role shrinks to (a) pulling stacked (PE, KE) logging rows
    and (b) triaging the health-flag lattice — both once per chunk
    (= logging boundary).  Positions, velocities, forces, topology, and
    the rebuild decision never leave the device.

    With a RecoveryPolicy the flag triage becomes recovery instead of a
    raise: capacity overflows regrow the grid (one re-jit per regrow)
    and roll back to the last good chunk; non-finite/escape/drift flags
    roll back and retry, halving dt after ``retries_before_dt_halve``
    plain retries — all bounded, with typed errors past the budget.
    Because a chunk's outputs are only *committed* to the carry after a
    clean health check, a flagged chunk never contaminates the
    trajectory: rollback is simply "keep the previous carry".

    The host's phases are spans of :mod:`repro.runtime.trace`: ``md.seed``
    (seed build and force), then per chunk ``md.chunk`` around
    ``md.hook`` (``fault_hook``), ``md.dispatch`` (the chunk call until it
    returns), ``md.wait`` (the flag read-back, where the host blocks until
    the chunk is done), ``md.log`` (rebuild count and thermo rows) and
    ``md.recover`` (regrow or rollback, when taken); ``run_nve`` wraps
    the whole call in ``md.run``.
    """
    from .resilience import (HealthReport, RecoveryEvent,
                             RecoveryExhaustedError, load_md_checkpoint,
                             regrow_grid, save_md_checkpoint)
    kw = force_kwargs or {}
    cache = fn_cache if fn_cache is not None else {}
    n_atoms = len(state.pos)
    events = cache.setdefault('recovery_events', [])
    rb = cfg.rcut + skin
    # max_nbors sizes the rcut sphere (host-path contract); scale the
    # padded width to the rcut+skin shell by the volume ratio
    k_build = int(np.ceil(max_nbors * (rb / cfg.rcut) ** 3 / 4.0)) * 4

    carry = None
    e_ref = 0.0
    dt_cur = float(dt)
    if restore:
        if not checkpoint_dir:
            raise ValueError('restore=True requires checkpoint_dir')
        carry_np, box, grid, manifest = load_md_checkpoint(checkpoint_dir)
        box = np.asarray(box, np.float64)
        if carry_np['pos'].shape[0] != n_atoms:
            raise ValueError(
                f"checkpoint holds {carry_np['pos'].shape[0]} atoms but "
                f'state has {n_atoms}')
        carry = {k: jnp.asarray(v) for k, v in carry_np.items()}
        state.step = int(manifest['step'])
        state.box = box
        e_ref = float(manifest['extra'].get('e_ref', 0.0))
        # dt is part of the continuation contract: a resilience dt-halving
        # before the snapshot must survive the restart
        dt_cur = float(manifest['extra'].get('dt', dt))
        cache['device_grid'] = grid
    else:
        box = np.asarray(state.box, np.float64)
        nbins = tuple(int(max(1, np.floor(b / rb))) for b in box)
        grid = cache.get('device_grid')
        if grid is None:
            cap = cell_cap or auto_cell_cap(state.pos, box, rb)
            grid = cache['device_grid'] = make_grid(box, cfg.rcut, skin,
                                                    cap, k_build)
        elif (grid.nbins != nbins or grid.max_nbors < k_build
              or grid.rcut != cfg.rcut or grid.skin != skin
              or (cell_cap is not None and grid.cell_cap < cell_cap)):
            # the grid fingerprint covers what the run_nve fingerprint
            # cannot: box geometry and list capacities.  Capacities may
            # legitimately *exceed* the request — a previous run under
            # this cache may have regrown them — but never undershoot.
            raise ValueError(
                'fn_cache device grid was built for a different '
                'box/max_nbors/cell_cap; pass a fresh dict')
    boxj = jnp.asarray(box)

    def sharded_force():
        if shards <= 1:
            return None
        if n_atoms % shards:
            raise ValueError(
                f'natoms={n_atoms} must divide by shards={shards}')
        fn = cache.get('device_sharded_force')
        if fn is None:
            from repro.kernels.ops import make_sharded_force_fn
            from repro.launch.sharding import make_atom_mesh
            fn = make_sharded_force_fn(
                cfg, beta, beta0, make_atom_mesh(shards), impl=impl, **kw)
            cache['device_sharded_force'] = fn
        return fn

    force_fn = sharded_force()
    max_regrows = policy.max_regrows if policy is not None else 0
    regrows = 0

    if carry is None:
        with span('md.seed'):
            pos = jnp.asarray(state.pos)
            vel = jnp.asarray(state.vel)
            while True:   # seed build, with bounded regrow under a policy
                nbr_idx, mask, shifts, fl = jitted_build(grid)(pos, boxj)
                report = HealthReport.from_flags(fl, grid)
                if not report.overflow:
                    break
                if policy is None:
                    check_flags(fl, grid)   # raises the legacy typed error
                if regrows >= max_regrows:
                    raise RecoveryExhaustedError(
                        'initial neighbor build still overflows after '
                        'regrowing', dict(step=state.step,
                                          issues=report.issues(),
                                          regrows=regrows))
                new_grid = regrow_grid(grid, report, policy)
                events.append(RecoveryEvent(
                    state.step, 'regrow',
                    dict(where='seed_build', issues=report.issues(),
                         cell_cap=(grid.cell_cap, new_grid.cell_cap),
                         max_nbors=(grid.max_nbors, new_grid.max_nbors))))
                grid = cache['device_grid'] = new_grid
                regrows += 1
            # seed the force carry once at step 0 (exact rcut cut, like every
            # step); jitted — an eager adjoint pipeline here would dominate
            # short runs
            e0, f = _seed_force(cache, cfg, beta, beta0, impl, kw, force_fn,
                                pos, nbr_idx, shifts, mask)
            ke0 = float(_mass_terms(mass)[1](vel))
            e_ref = float(e0) + ke0
            carry = dict(pos=pos, vel=vel, f=f, nbr_idx=nbr_idx,
                         shifts=shifts, mask=mask, pos_ref=pos,
                         flags=_full_flags(fl))

    chunks = cache.setdefault('device_chunks', {})
    counter = cache.setdefault('device_trace_count', {})
    thermo = []
    rebuilds = 0
    it = 0
    numeric_retries = 0
    steps_since_ckpt = 0
    chunk_len = max(1, min(log_every, n_steps))
    while it < n_steps:
        with span('md.chunk'):
            n_sub = min(chunk_len, n_steps - it)
            abs_step = state.step + it
            # chunk fns are keyed by every static they bake in: length,
            # grid capacities (regrows change array shapes), and dt
            # (resilience may halve it) — at most one trace per key
            key = (n_sub, grid.cell_cap, grid.max_nbors, dt_cur)
            if key not in chunks:
                chunks[key] = make_device_chunk_fn(
                    cfg, beta, beta0, dt_cur, mass, grid, impl, n_sub,
                    force_fn=force_fn, trace_counter=counter,
                    policy=policy, **kw)
            attempt = carry
            if fault_hook is not None:
                with span('md.hook'):
                    attempt = fault_hook(abs_step, carry, grid)
            with span('md.dispatch'):
                (pos, vel, f, nbr_idx, shifts, mask, pos_ref, flags, pe,
                 ke, nreb) = chunks[key](
                    attempt['pos'], attempt['vel'], attempt['f'], boxj,
                    attempt['nbr_idx'], attempt['shifts'], attempt['mask'],
                    attempt['pos_ref'], attempt['flags'],
                    jnp.float64(e_ref))
            # host boundary: health triage + logging rows, nothing else
            with span('md.wait'):
                if policy is None:
                    check_flags(flags, grid)
                else:
                    report = HealthReport.from_flags(flags, grid)
            if policy is not None and report.overflow:
                with span('md.recover'):
                    if regrows >= max_regrows:
                        raise RecoveryExhaustedError(
                            'capacity overflows persisted past the regrow '
                            'budget', dict(step=abs_step,
                                           issues=report.issues(),
                                           regrows=regrows))
                    new_grid = regrow_grid(grid, report, policy)
                    events.append(RecoveryEvent(
                        abs_step, 'regrow',
                        dict(issues=report.issues(),
                             cell_cap=(grid.cell_cap, new_grid.cell_cap),
                             max_nbors=(grid.max_nbors,
                                        new_grid.max_nbors))))
                    grid = cache['device_grid'] = new_grid
                    regrows += 1
                    # roll back to the last good chunk: rebuild the
                    # topology at the regrown capacities from the
                    # committed positions (the force carry is still valid
                    # — same positions)
                    ni, ms, sh, fl = jitted_build(grid)(carry['pos'], boxj)
                    carry = dict(carry, nbr_idx=ni, mask=ms, shifts=sh,
                                 pos_ref=carry['pos'],
                                 flags=_full_flags(fl))
                continue
            if policy is not None and report.numeric:
                with span('md.recover'):
                    if numeric_retries >= policy.max_numeric_retries:
                        raise report.numeric_error(
                            dict(step=abs_step, issues=report.issues(),
                                 retries=numeric_retries, dt=dt_cur))
                    events.append(RecoveryEvent(
                        abs_step, 'rollback',
                        dict(issues=report.issues(),
                             retries=numeric_retries)))
                    if numeric_retries >= policy.retries_before_dt_halve:
                        dt_cur *= 0.5
                        events.append(RecoveryEvent(abs_step, 'dt_halve',
                                                    dict(dt=dt_cur)))
                    numeric_retries += 1
                continue   # carry is still the last good chunk
            # clean chunk: commit to the carry and the thermo log
            carry = dict(pos=pos, vel=vel, f=f, nbr_idx=nbr_idx,
                         shifts=shifts, mask=mask, pos_ref=pos_ref,
                         flags=flags)
            numeric_retries = 0
            with span('md.log'):
                rebuilds += int(nreb)
                _log_rows(thermo, np.asarray(pe), np.asarray(ke), it,
                          state.step, n_atoms, n_steps, log_every)
            it += n_sub
            steps_since_ckpt += n_sub
            if (checkpoint_dir and checkpoint_every
                    and steps_since_ckpt >= checkpoint_every):
                path = save_md_checkpoint(
                    checkpoint_dir, state.step + it, carry, box, grid,
                    extra=dict(dt=dt_cur, e_ref=e_ref, n_atoms=n_atoms))
                events.append(RecoveryEvent(state.step + it, 'checkpoint',
                                            dict(path=str(path))))
                steps_since_ckpt = 0
    cache['device_rebuilds'] = rebuilds
    if cfg.species_path:
        count = cache.get('species_count')
        if count is None:
            count = cache['species_count'] = jax.jit(
                partial(species_pair_counts, cfg, kw['species']))
        cache['species_pairs'] = np.asarray(count(
            carry['pos_ref'], carry['nbr_idx'], carry['shifts'],
            carry['mask'])).tolist()
    state.pos = np.asarray(carry['pos'])
    state.vel = np.asarray(carry['vel'])
    state.step += n_steps
    return state, thermo


def _run_nve_host(cfg, beta, beta0, state, n_steps, dt, mass, impl,
                  rebuild_every, max_nbors, log_every, force_kwargs,
                  fn_cache=None):
    cache = fn_cache if fn_cache is not None else {}
    if 'force' not in cache:
        cache['force'] = make_force_fn(cfg, beta, beta0, impl,
                                       **(force_kwargs or {}))
    force_fn = cache['force']
    m = np.asarray(mass, np.float64)
    m = m[:, None] if m.ndim else m
    thermo = []
    nbr = None
    f = None
    e = None
    for it in range(n_steps):
        if it % rebuild_every == 0 or nbr is None:
            nbr_idx, mask, disp, _ = brute_neighbors(
                state.pos, state.box, cfg.rcut, max_nbors)
            nbr = (nbr_idx, mask)
            if f is None:   # only step 0 lacks a force; rebuilds keep the
                # carried force (same positions, refreshed topology)
                e, fj = force_fn(disp[..., 0], disp[..., 1], disp[..., 2],
                                 nbr_idx, mask)
                f = np.asarray(fj)
        # velocity verlet
        acc = f / m * ACC_CONV
        state.vel = state.vel + 0.5 * dt * acc
        state.pos = state.pos + dt * state.vel
        nbr_idx, mask = nbr
        disp = _recompute_disp(state.pos, state.box, nbr_idx)
        e, fj = force_fn(disp[..., 0], disp[..., 1], disp[..., 2],
                         nbr_idx, mask)
        f = np.asarray(fj)
        acc = f / m * ACC_CONV
        state.vel = state.vel + 0.5 * dt * acc
        state.step += 1
        if it % log_every == 0 or it == n_steps - 1:
            T, ke = temperature(state.vel, mass)
            thermo.append(dict(step=state.step, T=T, ke=ke,
                               pe=float(e), etot=float(e) + ke))
    return state, thermo


def _recompute_disp(pos, box, nbr_idx):
    d = pos[nbr_idx] - pos[:, None, :]
    return d - box * np.round(d / box)
