"""Jitted wrappers around the SNAP Pallas kernels + the kernel-backed
energy/forces pipeline (``impl='kernel'`` in :func:`repro.core.snap.energy_forces`).

The wrappers own all layout plumbing: [natoms, nnbor] padded neighbor lists
in, physics out — identical signatures to the pure-jnp pipelines so the MD
driver and benchmarks can swap implementations freely.

``snap_force_pipeline`` is the hot path: after the single entry conversion
into the canonical kernel layout ([*, natoms_pad] planes, atoms on lanes),
U -> Y -> fused dE runs entirely on-device in that layout — no complex
reassembly, transpose, or re-pad between stages (see DESIGN.md).  The only
layout conversions are the entry ([natoms, nnbor] -> [nnbor, 4, natoms_pad])
and the exit (per-pair dE -> global force assembly).

``layout='half'`` (the default) runs every stage on the symmetric
**half-index planes** ``[idxu_half_max, natoms_pad]``: the U kernel only
ever produces the left rows 2mb <= j, the Y kernel gathers/scatters the
halved space through mirror-folded COO tables, and the fused-dE kernel
consumes the half planes natively — no full-plane tensor exists between
entry and force assembly.  ``layout='full'`` keeps the v1 full-plane
pipeline alive for A/B benchmarking (see benchmarks/b_kernels.py).
``mxu_dtype`` (half layout only) sets the precision of the Y walk's
operands: ``jnp.bfloat16`` rounds its U rows, coefficients and products
to bfloat16, with f32 accumulation.  The half walk takes beta itself and
forms each entry's coefficient in the kernel; only the full-plane Y
kernel takes a per-entry coefficient table built at the JAX level
(``y_coef``).

Multi-element SNAP (``cfg.species_path``, half layout only) runs its own
three kernels, ``snap_u_species``, ``snap_y_species`` and
``snap_de_species``: the per-pair array gains a fifth channel (the pair's
cutoff) and carries the neighbour's element weight in its mask channel,
and Y selects each lane's coefficients from its element's row of beta.  The
single-element path keeps its kernels, operands and shapes.

Names in a profile: each kernel's ``pallas_call`` is named (``snap_u_half``,
``snap_y_half``, ``snap_fused_de_half``; ``snap_u``, ``snap_y``,
``snap_fused_de`` in the full layout; the species kernels above), which
names its custom-call instruction in the optimized HLO.  The glue between
them runs under the named scopes ``snap.layout``, ``snap.self_planes``,
``snap.y_coef`` (the full layout's coefficient table), ``snap.assemble``,
``snap.energy`` and (species path) ``snap.species``, which reach each
op's ``op_name`` (the profiler's ``tf_op``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.retrace import record_trace
from repro.core.geometry import sanitize_displacements
from repro.core.snap import (SnapConfig, _require_species, assemble_forces,
                             bzero_shift, species_coefficients,
                             species_pairs)

from .common import LANES
from .snap_fused_de import snap_fused_de_pallas
from .snap_fused_de_half import (snap_de_species_pallas,
                                 snap_fused_de_half_pallas)
from .snap_u import snap_u_half_pallas, snap_u_pallas, snap_u_species_pallas
from .snap_y import (Y_HALF_TILE, Y_TILE, snap_y_half_pallas, snap_y_pallas,
                     snap_y_species_pallas, y_coef)

LAYOUTS = ('half', 'full')


def _kernel_layout(cfg: SnapConfig, dx, dy, dz, mask, dtype):
    """[natoms, nnbor] displacement triplets -> [nnbor, 4, natoms_pad]."""
    dx, dy, dz, ok = sanitize_displacements(dx, dy, dz, mask,
                                            safe_r=0.5 * cfg.rcut)
    natoms = dx.shape[0]
    pad = (-natoms) % LANES
    disp = jnp.stack([dx.T, dy.T, dz.T, ok.T.astype(dx.dtype)], axis=1)
    disp = jnp.pad(disp, [(0, 0), (0, 0), (0, pad)]).astype(dtype)
    # dead lanes (atom padding) must still see a regular radius: the
    # Cayley-Klein map is singular at r = 0 even when masked out.
    m = disp[:, 3, :]
    disp = disp.at[:, 0, :].set(
        jnp.where(m > 0, disp[:, 0, :], 0.5 * cfg.rcut))
    return disp, ok, natoms


def _species_layout(cfg: SnapConfig, dx, dy, dz, mask, w_j, rc, dtype):
    """Species path: [natoms, nnbor] -> [nnbor, 5, natoms_pad] rows (x, y,
    z, w_j, rcut_ij).  Each slot is cut at its own pair's cutoff; the
    weight channel is 0 off the pair set, and every slot off it (and
    every dead lane) gets the regular radius and cutoff of the
    single-element layout."""
    mask = mask & (dx * dx + dy * dy + dz * dz < rc * rc)
    dx, dy, dz, ok = sanitize_displacements(dx, dy, dz, mask,
                                            safe_r=0.5 * cfg.rcut)
    natoms = dx.shape[0]
    pad = (-natoms) % LANES
    w = jnp.where(ok, w_j, 0.0)
    rc = jnp.where(ok, rc, cfg.rcut)
    disp = jnp.stack([dx.T, dy.T, dz.T, w.T, rc.T], axis=1)
    disp = jnp.pad(disp, [(0, 0), (0, 0), (0, pad)]).astype(dtype)
    live = jnp.pad(ok.T, [(0, 0), (0, pad)])
    disp = disp.at[:, 0, :].set(
        jnp.where(live, disp[:, 0, :], 0.5 * cfg.rcut))
    disp = disp.at[:, 4, :].set(jnp.where(live, disp[:, 4, :], cfg.rcut))
    return disp, ok, natoms


def _self_planes(cfg: SnapConfig, dtype, layout='full'):
    """Wigner self-contribution as a lane-broadcastable [*, 1] plane."""
    idx = cfg.index
    if layout == 'half':
        v = np.zeros(idx.idxu_half_max)
        v[idx.self_diag_half] = cfg.wself
    else:
        v = np.zeros(idx.idxu_max)
        v[idx.self_diag] = cfg.wself
    return jnp.asarray(v, dtype)[:, None]


def half_planes_to_full(cfg: SnapConfig, h_r, h_i):
    """Expand [idxu_half_max, *] half planes to full via the j-mirror:
    u_full = sign * conj^c(u_half[src]).  Test/benchmark plumbing only —
    the pipeline itself never reconstructs full planes."""
    idx = cfg.index
    sgn = jnp.asarray(idx.full_to_half_sign, h_r.dtype)[:, None]
    sig = jnp.asarray(
        np.where(idx.full_to_half_conj, -1.0, 1.0), h_i.dtype)[:, None]
    return sgn * h_r[idx.full_to_half], sgn * sig * h_i[idx.full_to_half]


def energy_from_ylist_lanes(cfg: SnapConfig, ut_r, ut_i, y_r, y_i,
                            beta, beta0, species=None):
    """Per-atom energy in kernel layout: (2/3) sum_jju w Re(conj(U) Y).

    Operands are [idxu_max, natoms_pad] or [idxu_half_max, natoms_pad]
    planes (selected by shape); the reduction runs over the sublane (jju)
    axis so the energy never leaves the kernel layout.  The half form is
    exact because ``dedr_weight`` is zero on every mirrored row.  Mirrors
    :func:`repro.core.snap.energy_from_ylist` exactly.

    species: [natoms_pad] element index per lane (species path), with
    ``beta`` [nelements, ncoeff] and ``beta0`` [nelements].
    """
    idx = cfg.index
    w = (idx.dedr_weight_half if ut_r.shape[0] == idx.idxu_half_max
         else idx.dedr_weight)
    w = jnp.asarray(w, ut_r.dtype)[:, None]
    e_raw = (2.0 / 3.0) * jnp.sum(w * (ut_r * y_r + ut_i * y_i), axis=0)
    if species is not None:
        shift = beta0 - bzero_shift(cfg, beta, e_raw.dtype)
        return e_raw + jnp.asarray(shift, e_raw.dtype)[species]
    return beta0 + e_raw - bzero_shift(cfg, beta, e_raw.dtype)


def snap_force_pipeline(cfg: SnapConfig, beta, beta0, dx, dy, dz, nbr_idx,
                        mask, dtype=jnp.float32, interpret=None,
                        with_energy=True, layout: str = 'half',
                        y_tile: int | None = None, mxu_dtype=None,
                        shard=None, species=None):
    """Zero-relayout kernel pipeline: Pallas U -> Pallas Y -> Pallas fused dE.

    Every inter-stage tensor stays in the canonical [*, natoms_pad] device
    layout.  The half layout's Y walk takes beta as it is (cast to
    ``dtype``) and forms each entry's coefficient ``cg * y_fac *
    beta[y_jjb]`` in the kernel, so nothing beta-dependent is built per
    call outside the kernels; the full layout's per-entry coefficient
    (``y_coef``, no atom axis) is its one stage input computed at the
    JAX level.

    layout='half' (default): all inter-stage planes are half-index
    ``[idxu_half_max, natoms_pad]`` — ~1.9x less HBM plane traffic, and
    Y is the sparse VPU walk over the half CG table; no full plane is
    ever materialized.
    layout='full': the v1 full-plane pipeline (one-hot MXU Y), kept for
    A/B measurement.

    y_tile: COO entries per Y grid step (default: the layout's own,
    ``Y_HALF_TILE`` or ``Y_TILE``).

    mxu_dtype: optional precision of the Y walk's operands (half layout
    only), e.g. ``jnp.bfloat16`` rounds its U rows, coefficients and
    products; accumulation stays in ``dtype``.

    shard: optional ``(axis_name, n_shards)`` for the atom-sharded path —
    the Pallas stages are untouched (atoms already live on the lane axis,
    per shard), only the exit force assembly reduce-scatters.

    species: the element index of every atom (global under ``shard``);
    a multi-element config runs the species kernels on it (half layout).
    """
    if _require_species(cfg, species):
        if layout != 'half':
            raise ValueError("the species path runs the half layout only")
        return _species_pipeline(cfg, beta, beta0, dx, dy, dz, nbr_idx,
                                 mask, species, dtype, interpret,
                                 with_energy, y_tile or Y_HALF_TILE,
                                 mxu_dtype, shard)
    if layout not in LAYOUTS:
        raise ValueError(f'unknown layout {layout!r}; choose from {LAYOUTS}')
    if mxu_dtype is not None and layout != 'half':
        raise ValueError(
            "mxu_dtype is a half-layout feature (the full-plane Y kernel "
            "has no low-precision path); drop it or use layout='half'")
    natoms = dx.shape[0]
    with jax.named_scope('snap.layout'):
        disp, ok, _ = _kernel_layout(cfg, dx, dy, dz, mask, dtype)
    geo = dict(twojmax=cfg.twojmax, rcut=cfg.rcut, rmin0=cfg.rmin0,
               rfac0=cfg.rfac0, switch_flag=cfg.switch_flag,
               interpret=interpret)

    y_tile = y_tile or (Y_HALF_TILE if layout == 'half' else Y_TILE)
    if layout == 'half':
        ut_r, ut_i = snap_u_half_pallas(disp, **geo)
        with jax.named_scope('snap.self_planes'):
            ut_r = ut_r + _self_planes(cfg, dtype, 'half')   # elementwise
        y_r, y_i = snap_y_half_pallas(ut_r, ut_i, beta, twojmax=cfg.twojmax,
                                      tile=y_tile, mxu_dtype=mxu_dtype,
                                      interpret=interpret)
        dedr = snap_fused_de_half_pallas(disp, y_r, y_i, **geo)
    else:
        ut_r, ut_i = snap_u_pallas(disp, **geo)
        with jax.named_scope('snap.self_planes'):
            ut_r = ut_r + _self_planes(cfg, dtype)           # elementwise
        with jax.named_scope('snap.y_coef'):
            coef = y_coef(beta, cfg.twojmax, y_tile).astype(dtype)
        y_r, y_i = snap_y_pallas(ut_r, ut_i, coef, twojmax=cfg.twojmax,
                                 tile=y_tile, interpret=interpret)
        dedr = snap_fused_de_pallas(disp, y_r, y_i, **geo)

    # pipeline exit: per-pair dE back to [natoms, nnbor, 3] force assembly
    axis_name, n_shards = shard if shard is not None else (None, 1)
    with jax.named_scope('snap.assemble'):
        dedr_pairs = dedr[:, :3, :natoms].transpose(2, 0, 1)
        forces = assemble_forces(dedr_pairs, nbr_idx, ok, natoms * n_shards,
                                 axis_name=axis_name)
    if not with_energy:
        return None, None, forces
    with jax.named_scope('snap.energy'):
        e_atom = energy_from_ylist_lanes(cfg, ut_r, ut_i, y_r, y_i,
                                         beta, beta0)[:natoms]
    return jnp.sum(e_atom), e_atom, forces


def _species_pipeline(cfg: SnapConfig, beta, beta0, dx, dy, dz, nbr_idx,
                      mask, species, dtype, interpret, with_energy, y_tile,
                      mxu_dtype, shard):
    """The species path of :func:`snap_force_pipeline` (half layout):
    per-pair weight and cutoff ride in the per-pair array, per-element
    coefficients in Y's SMEM, and each lane's element in a plane."""
    natoms = dx.shape[0]
    with jax.named_scope('snap.species'):
        sp_i, w_j, rc = species_pairs(cfg, species, nbr_idx, shard)
        beta, beta0 = species_coefficients(cfg, beta, beta0)
        sp_lanes = jnp.pad(sp_i, (0, (-natoms) % LANES))
    with jax.named_scope('snap.layout'):
        disp, ok, _ = _species_layout(cfg, dx, dy, dz, mask, w_j, rc, dtype)
    geo = dict(twojmax=cfg.twojmax, rmin0=cfg.rmin0, rfac0=cfg.rfac0,
               switch_flag=cfg.switch_flag, interpret=interpret)
    ut_r, ut_i = snap_u_species_pallas(disp, **geo)
    with jax.named_scope('snap.self_planes'):
        ut_r = ut_r + _self_planes(cfg, dtype, 'half')
    y_r, y_i = snap_y_species_pallas(ut_r, ut_i, beta, sp_lanes,
                                     twojmax=cfg.twojmax, tile=y_tile,
                                     mxu_dtype=mxu_dtype,
                                     interpret=interpret)
    dedr = snap_de_species_pallas(disp, y_r, y_i, **geo)

    axis_name, n_shards = shard if shard is not None else (None, 1)
    with jax.named_scope('snap.assemble'):
        dedr_pairs = dedr[:, :3, :natoms].transpose(2, 0, 1)
        forces = assemble_forces(dedr_pairs, nbr_idx, ok, natoms * n_shards,
                                 axis_name=axis_name)
    if not with_energy:
        return None, None, forces
    with jax.named_scope('snap.energy'):
        e_atom = energy_from_ylist_lanes(cfg, ut_r, ut_i, y_r, y_i, beta,
                                         beta0, sp_lanes)[:natoms]
    return jnp.sum(e_atom), e_atom, forces


# the dispatcher-facing name; kept as an alias for existing callers/tests
energy_forces_kernel = snap_force_pipeline


def make_sharded_force_fn(cfg: SnapConfig, beta, beta0, mesh, axis='data',
                          impl='adjoint', **kw):
    """Atom-sharded force pipeline: ``shard_map`` over ``mesh[axis]``.

    Returns a jitted ``fn(dx, dy, dz, nbr_idx, mask) -> (e, e_atom, f)``
    whose inputs/outputs have *global* atom leading dims (divisible by the
    axis size).  Each shard runs the chosen pipeline on its local atom rows
    — the Pallas kernels need no layout change because atoms already live
    on the lane axis per shard — and the cross-shard force pairs are summed
    by the reduce-scatter inside :func:`repro.core.snap.assemble_forces`.
    The total energy is psum-reduced and replicated.
    """
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.core.snap import energy_forces

    n_shards = int(mesh.shape[axis])

    def body(dx, dy, dz, nbr_idx, mask):
        e, e_atom, f = energy_forces(cfg, beta, beta0, dx, dy, dz, nbr_idx,
                                     mask, impl=impl,
                                     shard=(axis, n_shards), **kw)
        return jax.lax.psum(e, axis), e_atom, f

    # check_vma=False: pallas_call has no replication rule (jax#21577-style
    # workaround); correctness is covered by the sharded-parity tests
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis), P(axis), P(axis)),
                       out_specs=(P(), P(axis), P(axis)), check_vma=False)
    return jax.jit(sm)


def make_batched_force_fn(cfg: SnapConfig, n_pad: int, max_nbors: int,
                          impl: str = 'kernel', dtype=jnp.float32,
                          interpret=None, trace_counter=None, **kw):
    """Batched (vmapped) force-evaluation entry for the serving front end.

    Returns one jitted function

        fn(pos [B, n_pad, 3], box [B, 3], beta [B, ncoeff], beta0 [B],
           n_valid [B] int32) -> (e [B], forces [B, n_pad, 3],
                                  flags [B, N_FLAGS] int32)

    that evaluates ``B`` independent configurations per device step: each
    lane runs the fixed-shape device neighbor build
    (:func:`repro.md.cell_list.brute_neighbors_device`) followed by the
    chosen force pipeline, all under one ``jax.vmap`` — so a batch of
    same-bucket requests costs one compile and one dispatch.

    Per-lane health flags reuse the :mod:`repro.md.cell_list` lattice
    slots: ``FLAG_NBR_MAX`` carries the observed neighbor count (overflow
    when it exceeds ``max_nbors``), ``FLAG_NAN_STATE`` latches non-finite
    input positions, ``FLAG_NAN_FORCE`` non-finite output forces/energy.
    Because every lane's flags are reduced over that lane only, a
    poisoned or overflowing configuration marks *itself* and nothing
    else — the fault-isolation contract the request server builds on
    (lane independence is asserted bitwise in tests/test_serve.py).

    ``trace_counter`` follows the ``fn_cache['device_trace_count']``
    idiom of the MD driver: incremented once per (re)trace, so callers
    can prove the bucket table bounds the compile count.

    impl='kernel' forwards ``dtype``/``interpret``/**kw** to the Pallas
    pipeline; impl='adjoint' (the jnp reference path, the serving layer's
    quarantine target) takes no kernel knobs.
    """
    import jax

    from repro.core.snap import energy_forces
    from repro.md.cell_list import (FLAG_CELL_MAX, FLAG_NAN_FORCE,
                                    FLAG_NAN_STATE, FLAG_NBR_MAX, N_FLAGS,
                                    brute_neighbors_device)

    if impl == 'kernel':
        fkw = dict(dtype=dtype, interpret=interpret, **kw)
    else:
        fkw = dict(kw)

    def lane(pos, box, beta, beta0, n_valid):
        ok_atom = jnp.arange(n_pad, dtype=jnp.int32) < n_valid
        nbr_idx, mask, disp, bflags = brute_neighbors_device(
            pos, box, cfg.rcut, max_nbors, n_valid)
        nan_state = jnp.logical_not(jnp.all(jnp.isfinite(
            jnp.where(ok_atom[:, None], pos, 0.0))))
        _, e_atom, f = energy_forces(
            cfg, beta, beta0, disp[..., 0], disp[..., 1], disp[..., 2],
            nbr_idx, mask, impl=impl, **fkw)
        # padded atoms see zero neighbors but still carry the Wigner
        # self-energy; mask them out of both outputs
        f = jnp.where(ok_atom[:, None], f, 0.0)
        e = jnp.sum(jnp.where(ok_atom, e_atom, 0.0))
        nan_force = jnp.logical_not(
            jnp.all(jnp.isfinite(f)) & jnp.isfinite(e))
        flags = jnp.zeros(N_FLAGS, jnp.int32)
        flags = flags.at[FLAG_NBR_MAX].set(bflags[0])
        flags = flags.at[FLAG_CELL_MAX].set(bflags[1])
        flags = flags.at[FLAG_NAN_FORCE].set(nan_force.astype(jnp.int32))
        flags = flags.at[FLAG_NAN_STATE].set(nan_state.astype(jnp.int32))
        return e, f, flags

    def batched(pos, box, beta, beta0, n_valid):
        record_trace(trace_counter)
        return jax.vmap(lane)(pos, box, beta, beta0, n_valid)

    return jax.jit(batched)


# ---------------------------------------------------------------------------
# per-stage wrappers (tests / benchmarks; each owns its own layout plumbing)
# ---------------------------------------------------------------------------

def snap_ui_kernel(cfg: SnapConfig, dx, dy, dz, mask, dtype=jnp.float32,
                   interpret=None, layout: str = 'half'):
    """Ulisttot via the Pallas kernel: complex [natoms, idxu_max].

    layout='half' runs the half-plane kernel and mirror-expands the result
    (test/benchmark plumbing — the pipeline itself stays in half planes);
    layout='full' runs the v1 full-plane kernel.
    """
    disp, ok, natoms = _kernel_layout(cfg, dx, dy, dz, mask, dtype)
    geo = dict(twojmax=cfg.twojmax, rcut=cfg.rcut, rmin0=cfg.rmin0,
               rfac0=cfg.rfac0, switch_flag=cfg.switch_flag,
               interpret=interpret)
    if layout == 'half':
        h_r, h_i = snap_u_half_pallas(disp, **geo)
        h_r = h_r + _self_planes(cfg, dtype, 'half')
        ut_r, ut_i = half_planes_to_full(cfg, h_r, h_i)
    else:
        ut_r, ut_i = snap_u_pallas(disp, **geo)
        ut_r = ut_r + _self_planes(cfg, dtype)
    return (ut_r[:, :natoms] + 1j * ut_i[:, :natoms]).T


def snap_yi_kernel(cfg: SnapConfig, ulisttot, beta, dtype=jnp.float32,
                   interpret=None, y_tile: int | None = None,
                   layout: str = 'half', mxu_dtype=None):
    """Adjoint Y via the Pallas kernel: complex [natoms, idxu_max].

    Layout-converting wrapper around :func:`snap_y_[half_]pallas` for
    parity tests and stage benchmarks; the pipeline itself never leaves
    plane layout.  The half layout scatters its compacted output back into
    the full index space (mirrored rows stay 0, like ``compute_ylist``);
    the dropped weight-0 middle-row columns also read 0 — compare on the
    ``dedr_weight > 0`` support.
    """
    if mxu_dtype is not None and layout != 'half':
        raise ValueError("mxu_dtype requires layout='half'")
    idx = cfg.index
    natoms = ulisttot.shape[0]
    pad = (-natoms) % LANES
    ut = ulisttot[:, idx.half_to_full] if layout == 'half' else ulisttot
    y_tile = y_tile or (Y_HALF_TILE if layout == 'half' else Y_TILE)
    ut_r = jnp.pad(ut.real.T.astype(dtype), [(0, 0), (0, pad)])
    ut_i = jnp.pad(ut.imag.T.astype(dtype), [(0, 0), (0, pad)])
    if layout == 'half':
        y_r, y_i = snap_y_half_pallas(ut_r, ut_i, beta, twojmax=cfg.twojmax,
                                      tile=y_tile, mxu_dtype=mxu_dtype,
                                      interpret=interpret)
        y_h = (y_r[:, :natoms] + 1j * y_i[:, :natoms]).T
        out = jnp.zeros((natoms, idx.idxu_max), y_h.dtype)
        return out.at[:, idx.half_to_full].set(y_h)
    coef = y_coef(beta, cfg.twojmax, y_tile).astype(dtype)
    y_r, y_i = snap_y_pallas(ut_r, ut_i, coef, twojmax=cfg.twojmax,
                             tile=y_tile, interpret=interpret)
    return (y_r[:, :natoms] + 1j * y_i[:, :natoms]).T


def snap_dedr_kernel(cfg: SnapConfig, dx, dy, dz, mask, ylist,
                     dtype=jnp.float32, interpret=None,
                     layout: str = 'half'):
    """Fused dE/dr per pair via the Pallas kernel: [natoms, nnbor, 3].

    layout='half' (default) gathers the half rows of ``ylist`` and runs
    the native half-plane kernel (half recursion state AND half Y
    streams); 'full' is the v1 kernel mirroring every level.
    """
    idx = cfg.index
    disp, ok, natoms = _kernel_layout(cfg, dx, dy, dz, mask, dtype)
    pad = disp.shape[-1] - natoms
    geo = dict(twojmax=cfg.twojmax, rcut=cfg.rcut, rmin0=cfg.rmin0,
               rfac0=cfg.rfac0, switch_flag=cfg.switch_flag,
               interpret=interpret)
    if layout == 'half':
        yl = ylist[:, idx.half_to_full]
        y_r = jnp.pad(yl.real.T.astype(dtype), [(0, 0), (0, pad)])
        y_i = jnp.pad(yl.imag.T.astype(dtype), [(0, 0), (0, pad)])
        dedr = snap_fused_de_half_pallas(disp, y_r, y_i, **geo)
    else:
        y_r = jnp.pad(ylist.real.T.astype(dtype), [(0, 0), (0, pad)])
        y_i = jnp.pad(ylist.imag.T.astype(dtype), [(0, 0), (0, pad)])
        dedr = snap_fused_de_pallas(disp, y_r, y_i, **geo)
    return dedr[:, :3, :natoms].transpose(2, 0, 1)
