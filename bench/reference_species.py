"""Plain multi-element SNAP reference: energies, forces and local velocity
Verlet in float64 on the host CPU.

Written from Wood, Cusentino, Wirth and Thompson, Phys. Rev. B 99,
184305 (2019), and LAMMPS ``compute_ui`` with element terms
(``pair_style snap``, ``chemflag 0``), with the benchmark's own index
tables (``snapidx``) and the static helpers of ``reference.py``.  It
imports nothing of the program under test.

The configuration's element table (``config['species']``: radius R,
weight w and mass per element; ``snap['rcutfac']``) gives each pair its
cutoff and each neighbour its weight:

    rcut_ij = rcutfac (R_ti + R_tj),  used in theta0 and the switching
    function alike;
    U_i = wself I + sum_j w_tj fc(r_ij; rcut_ij) U(r_ij; rcut_ij);
    E_i = beta0_ti + sum_l beta_ti,l (B_l(i) - bzero_l).

Forces are -dE/dr by reverse-mode differentiation of each E_i with
respect to atom i's neighbour displacements, as in ``reference.py``.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

import reference
import snapidx

PI = reference.PI


def table(config: dict):
    """(rcut [S, S], weights [S]) of the element table."""
    radii = np.array([e['radius'] for e in config['species']], np.float64)
    weights = np.array([e['weight'] for e in config['species']], np.float64)
    rcutfac = float(config['snap']['rcutfac'])
    return rcutfac * (radii[:, None] + radii[None, :]), weights


def _key(config):
    s = config['snap']
    return (tuple(sorted(s.items())),
            tuple(tuple(sorted(e.items())) for e in config['species']))


def _coefficients(snap: dict, beta: np.ndarray, beta0: np.ndarray):
    """Per element: the bispectrum terms' weights times its beta, and its
    energy offset beta0 - bzero . beta."""
    tj = int(snap['twojmax'])
    terms = snapidx.bispectrum_terms(tj)
    beta = np.asarray(beta, np.float64)
    coef = terms.w[None, :] * beta[:, terms.comp]
    shift = np.zeros(len(beta))
    if snap['bzero_flag']:
        wself = float(snap['wself'])
        bz = np.array([wself ** 3 * (j + 1)
                       for (_, _, j) in snapidx.bispectrum_triples(tj)])
        shift = beta @ bz
    return coef, np.asarray(beta0, np.float64) - shift


def _atom_energy_fn(snap: dict, coef: np.ndarray, offset: np.ndarray):
    """E(d [K, 3], m [K], w [K], rc [K], t) of one atom of element t, in
    float64 jax; ``w`` and ``rc`` are its neighbours' weights and pair
    cutoffs."""
    import jax.numpy as jnp

    tj = int(snap['twojmax'])
    rmin0, rfac0 = float(snap['rmin0']), float(snap['rfac0'])
    wself, switch = float(snap['wself']), bool(snap['switch_flag'])
    levels = reference._u_levels(tj)
    terms = snapidx.bispectrum_terms(tj)
    diag = np.concatenate([snapidx.u_offset(j) + np.arange(j + 1) * (j + 2)
                           for j in range(tj + 1)])
    self_u = np.zeros(snapidx.u_size(tj))
    self_u[diag] = wself
    coef, offset = jnp.asarray(coef), jnp.asarray(offset)

    def energy(d, m, w, rc, t):
        r2 = jnp.sum(d * d, axis=-1)
        ok = m & (r2 > 1e-20) & (r2 < rc * rc)
        # slots off the pair set get a harmless vector and weight 0
        safe = jnp.stack([0.5 * rc, 0.0 * rc, 0.0 * rc], axis=-1)
        d = jnp.where(ok[:, None], d, safe)
        x, y, z = d[:, 0], d[:, 1], d[:, 2]
        r = jnp.sqrt(jnp.sum(d * d, axis=-1))
        theta0 = (r - rmin0) * rfac0 * PI / (rc - rmin0)
        z0 = r / jnp.tan(theta0)
        r0inv = 1.0 / jnp.sqrt(r * r + z0 * z0)
        a = r0inv * (z0 - 1j * z)
        b = r0inv * (y - 1j * x)
        if switch:
            sfac = 0.5 * (jnp.cos((r - rmin0) * PI / (rc - rmin0)) + 1.0)
        else:
            sfac = jnp.ones_like(r)
        sfac = jnp.where(ok, w * sfac, 0.0)
        ac, bc = jnp.conj(a)[:, None], jnp.conj(b)[:, None]
        prev = jnp.ones((d.shape[0], 1), jnp.complex128)
        layers = [prev]
        for (a_src, a_c, b_src, b_c, full_src, mirror, sign) in levels:
            left = ac * prev[:, a_src] * a_c + bc * prev[:, b_src] * b_c
            src = left[:, full_src]
            prev = jnp.where(mirror, sign * jnp.conj(src), src)
            layers.append(prev)
        u = jnp.concatenate(layers, axis=1)                    # [K, U]
        utot = jnp.sum(sfac[:, None] * u, axis=0) + self_u     # [U]
        prod = jnp.conj(utot[terms.d]) * utot[terms.s1] * utot[terms.s2]
        return offset[t] + jnp.sum(coef[t] * prod.real)

    return energy


@lru_cache(maxsize=4)
def _compiled(key, beta_key, beta0_key, nelem):
    import jax
    snap = dict(key[0])
    beta = np.frombuffer(beta_key).reshape(nelem, -1)
    beta0 = np.frombuffer(beta0_key)
    coef, offset = _coefficients(snap, beta, beta0)
    energy = _atom_energy_fn(snap, coef, offset)
    return jax.jit(jax.vmap(jax.value_and_grad(energy)))


def _pair_data(config, species, centres, idx):
    rcut, weights = table(config)
    sp = np.asarray(species)
    return weights[sp[idx]], rcut[sp[np.asarray(centres)][:, None], sp[idx]]


def energies_and_grads(config, beta, beta0, species, centres, idx, disp,
                       mask, block=8):
    """(E_i [C], dE_i/d disp_ij [C, K, 3]) for each centre, in blocks."""
    import jax
    beta = np.asarray(beta, np.float64)
    beta0 = np.broadcast_to(np.asarray(beta0, np.float64), (len(beta),))
    fn = _compiled(_key(config), beta.tobytes(), beta0.copy().tobytes(),
                   len(beta))
    w, rc = _pair_data(config, species, centres, idx)
    t = np.asarray(species)[np.asarray(centres)]
    c = disp.shape[0]
    pad = (-c) % block

    def padded(a, fill=0):
        return np.concatenate([a, np.full((pad,) + a.shape[1:], fill,
                                          a.dtype)])
    rc_max = float(table(config)[0].max())
    disp, mask, w = padded(disp), padded(mask), padded(w)
    rc, t = padded(rc, rc_max), padded(t)
    cpu = jax.devices('cpu')[0]
    es, gs = [], []
    with jax.default_device(cpu), jax.default_matmul_precision('highest'):
        for lo in range(0, c + pad, block):
            sl = slice(lo, lo + block)
            e, g = fn(*(jax.device_put(a[sl], cpu)
                        for a in (disp, mask, w, rc, t)))
            es.append(np.asarray(e))
            gs.append(np.asarray(g))
    return np.concatenate(es)[:c], np.concatenate(gs)[:c]


def forces_on(config, beta, beta0, pos, box, species, atoms, block=8,
              centre_energies=False):
    """(per-atom energies [S], forces [S, 3]) of ``atoms``, float64.

    Lists are built at the largest pair cutoff; each pair is cut at its
    own inside the energy.  F_k = sum_j dE_k/dd_kj - sum_{i: k in N(i)}
    dE_i/dd_ik.  With ``centre_energies`` it also returns the atoms whose
    energies were differentiated (``atoms`` and their neighbours) and
    those energies.
    """
    atoms = np.asarray(atoms)
    rc_max = float(table(config)[0].max())
    idx_k, _, mask_k = reference.neighbours(pos, box, atoms, rc_max)
    centres = np.unique(np.concatenate([atoms, idx_k[mask_k]]))
    idx, disp, mask = reference.neighbours(pos, box, centres, rc_max)
    e, g = energies_and_grads(config, beta, beta0, species, centres, idx,
                              disp, mask, block)
    row = {int(c): n for n, c in enumerate(centres)}
    f = np.zeros((len(atoms), 3))
    e_out = np.zeros(len(atoms))
    for s, k in enumerate(atoms):
        r = row[int(k)]
        e_out[s] = e[r]
        f[s] = g[r][mask[r]].sum(0)
        for i in idx[r][mask[r]]:
            ri = row[int(i)]
            hit = mask[ri] & (idx[ri] == k)
            f[s] -= g[ri][hit].sum(0)
    if centre_energies:
        return e_out, f, centres, e
    return e_out, f


def verlet_local(config, beta, beta0, pos, vel, box, species, atoms, steps,
                 dt, acc_scale, block=8, layers=2):
    """Positions of ``atoms`` after ``steps`` velocity-Verlet steps from
    (pos, vel), with ``acc_scale`` [N] (ACC_CONV / mass of each atom),
    integrating only ``atoms`` and their neighbourhood.

    The moving set is ``layers`` shells of neighbours (within the largest
    cutoff) around ``atoms``, integrated under float64 reference forces;
    the shell around it moves at the constant acceleration of its
    reference force at the start, every other atom in free flight.  The
    shell's error reaches ``atoms`` only through the moving layers.
    ``reference.verlet_local`` integrates one layer, which is enough for
    300 K tungsten; the light Be atoms of a W-Be box jerk enough that one
    layer errs by up to 1e-5 of the displacement over ten 0.5 fs steps
    (against whole-box Verlet), so two are the default here."""
    rc_max = float(table(config)[0].max())
    acc_scale = np.asarray(acc_scale, np.float64)

    def forces(frame, which):
        if not len(which):          # a box that the moving set fills
            return np.zeros((0, 3))
        return forces_on(config, beta, beta0, frame, box, species, which,
                         block)[1] * acc_scale[which][:, None]

    def grow(core):
        idx, _, mask = reference.neighbours(pos, box, core, rc_max)
        return np.unique(np.concatenate([core, idx[mask]]))

    moving = np.asarray(atoms)
    for _ in range(layers):
        moving = grow(moving)
    shell = np.setdiff1d(grow(moving), moving)
    x0, v0 = np.asarray(pos, np.float64), np.asarray(vel, np.float64)
    x, v = x0[moving].copy(), v0[moving].copy()
    a_shell = forces(x0, shell)
    a = forces(x0, moving)
    for s in range(1, steps + 1):
        v += 0.5 * dt * a
        x += dt * v
        t = s * dt
        frame = x0 + t * v0
        frame[shell] += 0.5 * t * t * a_shell
        frame[moving] = x
        a = forces(frame, moving)
        v += 0.5 * dt * a
    row = {int(m): n for n, m in enumerate(moving)}
    return x[[row[int(m)] for m in atoms]]


def energy_and_forces(config, beta, beta0, pos, box, species, block=8):
    """Total energy and forces of every atom of a small configuration."""
    n = len(pos)
    e, f = forces_on(config, beta, beta0, pos, box, species, np.arange(n),
                     block)
    return float(e.sum()), f
