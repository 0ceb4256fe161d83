"""Ahead-of-time compiles of the SNAP Pallas kernels for a TPU v5e.

The TPU compiler is installed beside JAX, and it compiles for a chip that
is described rather than attached.  These tests compile the main-path
(half-layout) kernels at the paper's sizes (N=2000 -> 2048 lanes, 26
neighbours, f32) and so catch what interpret mode accepts but Mosaic
refuses: block shapes off the (8, 128) tiling, unlowerable primitives,
int64/f64 values under ``jax_enable_x64`` (on in this suite), and kernels
over the scoped-VMEM limit.  Nothing runs, so results and times are out of
scope here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and it keeps it until it
exits.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.indices import build_index
from repro.kernels.snap_fused_de_half import (snap_de_species_pallas,
                                              snap_fused_de_half_pallas)
from repro.kernels.snap_u import snap_u_half_pallas, snap_u_species_pallas
from repro.kernels.snap_y import (Y_TILE, _y_coo_tiles, snap_y_half_pallas,
                                  snap_y_pallas, snap_y_species_pallas)

NATOMS_PAD, NNBOR = 2048, 26
GEO = dict(rcut=4.7, rmin0=0.0, rfac0=0.99363, switch_flag=True,
           interpret=False)


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:
        pytest.skip(f'no v5e:2x2 topology can be described here: {e}')


@pytest.fixture(scope='module')
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope='module', autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    cc.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    cc.reset_cache()


def compile_for(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text.count('custom_call_target="tpu_custom_call"')


def ncoeff(twojmax):
    return int(build_index(twojmax).y_jjb.max()) + 1


def kernel_case(name, twojmax, natoms_pad=NATOMS_PAD):
    """(fn, shapes) of one kernel at the paper's sizes: half layout, except
    ``y_full`` (the full-plane A/B Y kernel).  The half Y walk takes beta
    itself, which it holds whole in SMEM."""
    f32 = jnp.float32
    nh = build_index(twojmax).idxu_half_max
    disp = ((NNBOR, 4, natoms_pad), f32)
    plane = ((nh, natoms_pad), f32)
    if name == 'u':
        return (lambda d: snap_u_half_pallas(d, twojmax=twojmax, **GEO),
                [disp])
    if name == 'de':
        return (lambda d, yr, yi: snap_fused_de_half_pallas(
            d, yr, yi, twojmax=twojmax, **GEO), [disp, plane, plane])
    if name == 'y_full':
        nu = build_index(twojmax).idxu_max
        ntiles = _y_coo_tiles(twojmax, Y_TILE)[0].shape[0]
        return (lambda ur, ui, c: snap_y_pallas(
            ur, ui, c, twojmax=twojmax, interpret=False),
            [((nu, NATOMS_PAD), f32)] * 2 + [((ntiles, 1, Y_TILE), f32)])
    mxu = jnp.bfloat16 if name == 'y_bf16' else None
    return (lambda ur, ui, b: snap_y_half_pallas(
        ur, ui, b, twojmax=twojmax, mxu_dtype=mxu, interpret=False),
        [plane, plane, ((ncoeff(twojmax),), f32)])


@pytest.mark.parametrize('name,twojmax', [
    ('y_f32', 8), ('y_bf16', 8), ('y_f32', 14), ('y_bf16', 14),
    ('u', 8), ('de', 8),
    ('u', 14),          # refused over the 16 MiB scoped-VMEM limit unrolled
    ('de', 14),
    ('y_full', 14),     # needs its raised scoped-VMEM limit (Y_VMEM_LIMIT)
])
def test_kernel_compiles_for_v5e(one_chip, name, twojmax):
    fn, shapes = kernel_case(name, twojmax)
    assert compile_for(one_chip, fn, *shapes) == 1


@pytest.mark.parametrize('name', ['y_f32', 'y_bf16'])
def test_y_walk_compiles_for_md_box(one_chip, name):
    """The half Y walk at the MD cell's 2J=8 box, 16,000 atoms: 125 lane
    tiles in blocks of 32, the last one partial."""
    fn, shapes = kernel_case(name, 8, natoms_pad=16000)
    assert compile_for(one_chip, fn, *shapes) == 1


@pytest.fixture(scope='module')
def force_pipeline_hlo(one_chip):
    """Optimized HLO of the whole f32 kernel force call at 2J=8 (half
    layout), compiled once for the tests that read it."""
    from repro.core.snap import SnapConfig, energy_forces
    cfg = SnapConfig(twojmax=8, rcut=4.7)
    f32 = jnp.float32
    pair = (2000, NNBOR)
    shapes = [((cfg.ncoeff,), f32), (pair, f32), (pair, f32), (pair, f32),
              (pair, jnp.int32), (pair, jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(
        lambda b, dx, dy, dz, ni, m: energy_forces(
            cfg, b, 0.0, dx, dy, dz, ni, m, impl='kernel', interpret=False)
    ).lower(*args).compile().as_text()


def test_force_pipeline_compiles_for_v5e(force_pipeline_hlo):
    """The whole f32 kernel force call at 2J=8 holds exactly the three
    Mosaic kernels (U, Y, fused dE) — none is left to interpret mode."""
    assert force_pipeline_hlo.count(
        'custom_call_target="tpu_custom_call"') == 3


def test_force_pipeline_kernels_carry_their_names(force_pipeline_hlo):
    """Each Mosaic kernel's instruction is named after its pallas_call, so
    a profile's op text finds it by name alone: exactly one custom-call
    each named %snap_u_half.*, %snap_y_half.*, %snap_fused_de_half.*."""
    names = re.findall(r'^\s*(?:ROOT )?%([\w.-]+) = .*'
                       r'custom_call_target="tpu_custom_call"',
                       force_pipeline_hlo, flags=re.M)
    kernels = ('snap_u_half', 'snap_y_half', 'snap_fused_de_half')
    for kernel in kernels:
        assert len([n for n in names
                    if re.fullmatch(rf'{kernel}\.\d+', n)]) == 1, names
    assert len(names) == len(kernels)


def test_y_kernel_keeps_its_plane_interface(force_pipeline_hlo):
    """Y's custom call gives two f32[H,N] planes and takes U's f32[H,N]
    planes among its operands: the interface by which a profile's reader
    tells Y from the other kernels."""
    line, = [ln for ln in force_pipeline_hlo.splitlines()
             if re.search(r'%snap_y_half\.\d+ = ', ln)
             and 'tpu_custom_call' in ln]
    out = re.search(r'= \(f32\[([\d,]+)\]\S*, f32\[\1\]\S*\) custom-call\(',
                    line)
    assert out, line[:300]
    hn = out.group(1)
    assert hn == f'{build_index(8).idxu_half_max},{NATOMS_PAD}', hn
    operands = re.search(
        r'operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}', line)
    assert operands.group(1).count(f'f32[{hn}]') == 2, operands.group(1)


def species_case(name, twojmax, natoms_pad, nnbor):
    """(fn, shapes) of one species kernel: the five-channel per-pair
    array, two elements' beta rows and the lane element plane."""
    f32 = jnp.float32
    geo = dict(rmin0=0.0, rfac0=0.99363, switch_flag=True, interpret=False)
    nh = build_index(twojmax).idxu_half_max
    disp = ((nnbor, 5, natoms_pad), f32)
    plane = ((nh, natoms_pad), f32)
    if name == 'u':
        return (lambda d: snap_u_species_pallas(d, twojmax=twojmax, **geo),
                [disp])
    if name == 'de':
        return (lambda d, yr, yi: snap_de_species_pallas(
            d, yr, yi, twojmax=twojmax, **geo), [disp, plane, plane])
    return (lambda ur, ui, b, s: snap_y_species_pallas(
        ur, ui, b, s, twojmax=twojmax, interpret=False),
        [plane, plane, ((2, ncoeff(twojmax)), f32),
         ((natoms_pad,), jnp.int32)])


@pytest.mark.parametrize('name', ['u', 'y', 'de'])
@pytest.mark.parametrize('natoms_pad,nnbor', [(NATOMS_PAD, NNBOR),
                                              (16000, 72)])
def test_species_kernel_compiles_for_v5e(one_chip, name, natoms_pad, nnbor):
    """The species kernels at 2J=8: the paper's box and the W-Be MD
    cell's 16,000 atoms with 72-slot lists."""
    fn, shapes = species_case(name, 8, natoms_pad, nnbor)
    assert compile_for(one_chip, fn, *shapes) == 1


def test_species_force_pipeline_compiles_for_v5e(one_chip):
    """The whole two-element kernel force call at 2J=8 holds exactly the
    three species kernels, each named after its pallas_call."""
    from repro.core.snap import SnapConfig, energy_forces
    cfg = SnapConfig(twojmax=8, rcutfac=4.8123, radii=(0.5, 0.417932),
                     weights=(1.0, 0.959049))
    f32 = jnp.float32
    pair = (2000, NNBOR)
    species = jnp.asarray(np.arange(2000) % 5 == 0, jnp.int32)
    shapes = [((2, cfg.ncoeff), f32), (pair, f32), (pair, f32), (pair, f32),
              (pair, jnp.int32), (pair, jnp.bool_)]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    hlo = jax.jit(
        lambda b, dx, dy, dz, ni, m: energy_forces(
            cfg, b, 0.0, dx, dy, dz, ni, m, impl='kernel', interpret=False,
            species=species)
    ).lower(*args).compile().as_text()
    names = re.findall(r'^\s*(?:ROOT )?%([\w.-]+) = .*'
                       r'custom_call_target="tpu_custom_call"', hlo,
                       flags=re.M)
    kernels = ('snap_u_species', 'snap_y_species', 'snap_de_species')
    assert sorted(n.rsplit('.', 1)[0] for n in names) == sorted(kernels)
