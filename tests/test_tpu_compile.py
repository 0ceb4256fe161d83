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
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.indices import build_index
from repro.kernels.snap_fused_de_half import snap_fused_de_half_pallas
from repro.kernels.snap_u import snap_u_half_pallas
from repro.kernels.snap_y import (Y_TILE, _y_coo_tiles, _y_half_coo_tiles,
                                  snap_y_half_pallas, snap_y_pallas)

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


def kernel_case(name, twojmax):
    """(fn, shapes) of one kernel at the paper's sizes: half layout, except
    ``y_full`` (the full-plane A/B Y kernel)."""
    f32 = jnp.float32
    nh = build_index(twojmax).idxu_half_max
    disp = ((NNBOR, 4, NATOMS_PAD), f32)
    plane = ((nh, NATOMS_PAD), f32)
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
    ntiles = _y_half_coo_tiles(twojmax, Y_TILE)[0].shape[0]
    mxu = jnp.bfloat16 if name == 'y_bf16' else None
    return (lambda ur, ui, c: snap_y_half_pallas(
        ur, ui, c, twojmax=twojmax, mxu_dtype=mxu, interpret=False),
        [plane, plane, ((ntiles, 1, Y_TILE), f32)])


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


def test_force_pipeline_compiles_for_v5e(one_chip):
    """The whole f32 kernel force call at 2J=8 holds exactly the three
    Mosaic kernels (U, Y, fused dE) — none is left to interpret mode."""
    from repro.core.snap import SnapConfig, energy_forces
    cfg = SnapConfig(twojmax=8, rcut=4.7)
    f32 = jnp.float32
    pair = (2000, NNBOR)
    n = compile_for(
        one_chip,
        lambda b, dx, dy, dz, ni, m: energy_forces(
            cfg, b, 0.0, dx, dy, dz, ni, m, impl='kernel', interpret=False),
        ((cfg.ncoeff,), f32), (pair, f32), (pair, f32), (pair, f32),
        (pair, jnp.int32), (pair, jnp.bool_))
    assert n == 3
