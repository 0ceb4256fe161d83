"""Plain SNAP reference: energies and forces in float64 on the host CPU.

Written from the published description (Thompson et al., J. Comput.
Phys. 285 (2015); LAMMPS ``compute_ui``/``compute_zi``/``compute_bi``)
with the benchmark's own index tables (``snapidx``).  It imports nothing
of the program under test and takes nothing the program made: the
neighbour lists come from the positions by the minimum-image convention,
the CG coefficients from ``snapidx``.

The energy of atom i is

    E_i = beta0 + sum_l beta_l (B_l(i) - bzero_l),
    B_l = 2 sum_half w Re(conj(U_j) Z_{j1 j2 j}),  Z = sum cg cg U_j1 U_j2,

with U the switching-weighted sum of the neighbours' Wigner-U matrices
plus the self term.  Forces are -dE/dr by reverse-mode differentiation of
E_i with respect to atom i's neighbour displacements (an independent
route from the program's adjoint), assembled over the neighbourhood of
each atom asked for.  Work is done in blocks of atoms, so it fits any
host memory.
"""

from __future__ import annotations

from functools import lru_cache

import numpy as np

import snapidx

PI = 3.141592653589793


@lru_cache(maxsize=None)
def _u_levels(twojmax: int):
    """Per layer j: static maps of the recursion (LAMMPS compute_uarray)
    u_j(mb, ma) = sqrt((j-ma)/(j-mb)) conj(a) u_{j-1}(mb, ma)
                - sqrt(ma/(j-mb))     conj(b) u_{j-1}(mb, ma-1)
    for rows 2mb <= j, and the mirror for the others."""
    out = []
    for j in range(1, twojmax + 1):
        rows = j // 2 + 1
        a_src = np.zeros(rows * (j + 1), np.int32)
        b_src = np.zeros_like(a_src)
        a_c = np.zeros(rows * (j + 1))
        b_c = np.zeros_like(a_c)
        for mb in range(rows):
            for ma in range(j + 1):
                e = mb * (j + 1) + ma
                if ma < j:
                    a_src[e], a_c[e] = mb * j + ma, np.sqrt((j - ma) / (j - mb))
                if ma > 0:
                    b_src[e], b_c[e] = (mb * j + ma - 1,
                                        -np.sqrt(ma / (j - mb)))
        full_src = np.zeros((j + 1) ** 2, np.int32)
        mirror = np.zeros((j + 1) ** 2, bool)
        sign = np.ones((j + 1) ** 2)
        for mb in range(j + 1):
            for ma in range(j + 1):
                f = mb * (j + 1) + ma
                if 2 * mb <= j:
                    full_src[f] = f
                else:
                    full_src[f] = (j - mb) * (j + 1) + (j - ma)
                    mirror[f] = True
                    sign[f] = (-1.0) ** (mb + ma)
        out.append((a_src, a_c, b_src, b_c, full_src, mirror, sign))
    return out


def _atom_energy_fn(snap: dict, beta: np.ndarray, beta0: float):
    """E(d [K, 3], m [K]) for one atom, in float64 jax."""
    import jax.numpy as jnp

    tj = int(snap['twojmax'])
    rcut, rmin0 = float(snap['rcut']), float(snap['rmin0'])
    rfac0, wself = float(snap['rfac0']), float(snap['wself'])
    switch = bool(snap['switch_flag'])
    levels = _u_levels(tj)
    terms = snapidx.bispectrum_terms(tj)
    w = terms.w * np.asarray(beta, np.float64)[terms.comp]
    diag = np.concatenate([snapidx.u_offset(j) + np.arange(j + 1) * (j + 2)
                           for j in range(tj + 1)])
    self_u = np.zeros(snapidx.u_size(tj))
    self_u[diag] = wself
    shift = 0.0
    if snap['bzero_flag']:
        bz = np.array([wself ** 3 * (j + 1)
                       for (_, _, j) in snapidx.bispectrum_triples(tj)])
        shift = float(np.dot(bz, beta))

    def energy(d, m):
        r2 = jnp.sum(d * d, axis=-1)
        ok = m & (r2 > 1e-20) & (r2 < rcut * rcut)
        # padded slots get a harmless vector and weight 0
        d = jnp.where(ok[:, None], d, jnp.array([0.5 * rcut, 0.0, 0.0]))
        x, y, z = d[:, 0], d[:, 1], d[:, 2]
        r = jnp.sqrt(jnp.sum(d * d, axis=-1))
        theta0 = (r - rmin0) * rfac0 * PI / (rcut - rmin0)
        z0 = r / jnp.tan(theta0)
        r0inv = 1.0 / jnp.sqrt(r * r + z0 * z0)
        a = r0inv * (z0 - 1j * z)
        b = r0inv * (y - 1j * x)
        if switch:
            sfac = 0.5 * (jnp.cos((r - rmin0) * PI / (rcut - rmin0)) + 1.0)
        else:
            sfac = jnp.ones_like(r)
        sfac = jnp.where(ok, sfac, 0.0)
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
        return beta0 + jnp.sum(w * prod.real) - shift

    return energy


def neighbours(pos, box, centres, rcut):
    """Minimum-image neighbour lists of ``centres`` over all atoms:
    (idx [C, K], disp [C, K, 3], mask [C, K]) with disp = r_j - r_i."""
    pos = np.asarray(pos, np.float64)
    box = np.asarray(box, np.float64)
    d = pos[None, :, :] - pos[np.asarray(centres)][:, None, :]
    d -= box * np.round(d / box)
    r2 = np.sum(d * d, axis=-1)
    within = (r2 < rcut * rcut) & (r2 > 1e-20)
    k = max(1, int(within.sum(1).max()))
    idx = np.zeros((len(centres), k), np.int64)
    disp = np.zeros((len(centres), k, 3))
    mask = np.zeros((len(centres), k), bool)
    for c in range(len(centres)):
        js = np.nonzero(within[c])[0]
        idx[c, :len(js)] = js
        disp[c, :len(js)] = d[c, js]
        mask[c, :len(js)] = True
    return idx, disp, mask


@lru_cache(maxsize=4)
def _compiled(snap_key, beta_key, beta0, block):
    import jax
    snap = dict(snap_key)
    energy = _atom_energy_fn(snap, np.frombuffer(beta_key), beta0)
    cpu = jax.devices('cpu')[0]
    f = jax.jit(jax.vmap(jax.value_and_grad(energy)))
    return f, cpu


def energies_and_grads(snap, beta, beta0, disp, mask, block=8):
    """(E_i [C], dE_i/d disp_ij [C, K, 3]) for each centre, in blocks."""
    import jax
    key = tuple(sorted(snap.items()))
    fn, cpu = _compiled(key, np.asarray(beta, np.float64).tobytes(),
                        float(beta0), block)
    c = disp.shape[0]
    pad = (-c) % block
    disp = np.concatenate([disp, np.zeros((pad,) + disp.shape[1:])])
    mask = np.concatenate([mask, np.zeros((pad,) + mask.shape[1:], bool)])
    es, gs = [], []
    with jax.default_device(cpu):
        for lo in range(0, c + pad, block):
            e, g = fn(jax.device_put(disp[lo:lo + block], cpu),
                      jax.device_put(mask[lo:lo + block], cpu))
            es.append(np.asarray(e))
            gs.append(np.asarray(g))
    return np.concatenate(es)[:c], np.concatenate(gs)[:c]


def forces_on(snap, beta, beta0, pos, box, atoms, block=8,
              centre_energies=False):
    """(per-atom energies [S], forces [S, 3]) of ``atoms``, float64.

    F_k = sum_j dE_k/dd_kj - sum_{i: k in N(i)} dE_i/dd_ik: the energies
    of k and of every neighbour of k are differentiated.  With
    ``centre_energies`` it also returns those atoms (``atoms`` and their
    neighbours, sorted) and their energies.
    """
    atoms = np.asarray(atoms)
    rcut = float(snap['rcut'])
    idx_k, _, mask_k = neighbours(pos, box, atoms, rcut)
    centres = np.unique(np.concatenate([atoms, idx_k[mask_k]]))
    idx, disp, mask = neighbours(pos, box, centres, rcut)
    e, g = energies_and_grads(snap, beta, beta0, disp, mask, block)
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


def verlet_local(snap, beta, beta0, pos, vel, box, atoms, steps, dt,
                 acc_scale, block=8):
    """Positions of ``atoms`` after ``steps`` velocity-Verlet steps from
    (pos, vel), integrating only ``atoms`` and their neighbours.

    The moving set is the atoms within rcut of ``atoms``, under float64
    reference forces.  The shell around it (the atoms within rcut of the
    moving set) moves at the constant acceleration of its reference force
    at the start, and every other atom in free flight; neither reaches
    the forces on ``atoms`` but through the moving set.  Over ten 0.5 fs
    steps of 300 K tungsten the error that makes in the positions of
    ``atoms`` is about 1e-8 of their displacement (against whole-box
    Verlet on a 432-atom box at 2J=8).
    """
    rcut = float(snap['rcut'])
    idx, _, mask = neighbours(pos, box, atoms, rcut)
    moving = np.unique(np.concatenate([np.asarray(atoms), idx[mask]]))
    idx, _, mask = neighbours(pos, box, moving, rcut)
    shell = np.setdiff1d(idx[mask], moving)
    x0, v0 = np.asarray(pos, np.float64), np.asarray(vel, np.float64)
    x, v = x0[moving].copy(), v0[moving].copy()
    _, f_shell = forces_on(snap, beta, beta0, x0, box, shell, block)
    a_shell = acc_scale * f_shell
    frame = x0.copy()
    _, f = forces_on(snap, beta, beta0, frame, box, moving, block)
    for s in range(1, steps + 1):
        v += 0.5 * dt * acc_scale * f
        x += dt * v
        t = s * dt
        frame = x0 + t * v0
        frame[shell] += 0.5 * t * t * a_shell
        frame[moving] = x
        _, f = forces_on(snap, beta, beta0, frame, box, moving, block)
        v += 0.5 * dt * acc_scale * f
    row = {int(a): n for n, a in enumerate(moving)}
    return x[[row[int(a)] for a in atoms]]


def energy_and_forces(snap, beta, beta0, pos, box, block=8):
    """Total energy and forces of every atom of a small configuration."""
    n = len(pos)
    e, f = forces_on(snap, beta, beta0, pos, box, np.arange(n), block)
    return float(e.sum()), f
