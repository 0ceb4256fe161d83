"""Whole runs on the CPU at a tiny size, past the harness's look for a
chip: sound, they come out correct; with the timed path broken
underneath, or with the bf16 control in the program's place, they come
out not correct."""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench_tiny import SAMPLE_BLOCK, run_cell  # noqa: E402

CELLS = ['force_2j14_bcc2k', 'md_2j8_bcc16k']


@pytest.fixture(autouse=True)
def _x64():
    import jax
    jax.config.update('jax_enable_x64', True)


@pytest.mark.parametrize('cell', CELLS)
def test_sound_run_is_correct(cell):
    correct, line = run_cell(cell)
    assert correct and line['correct'] and line['failed'] == 0
    assert list(line)[-1] == 'compared'


@pytest.mark.parametrize('cell', CELLS)
def test_bf16_control_is_not_correct(cell, monkeypatch):
    import jax.numpy as jnp
    from repro.kernels import ops
    real = ops.snap_force_pipeline

    def bf16(*a, **kw):
        kw['mxu_dtype'] = jnp.bfloat16
        return real(*a, **kw)
    monkeypatch.setattr(ops, 'snap_force_pipeline', bf16)
    correct, line = run_cell(cell)
    assert not correct and not line['correct']
    assert line['compared']['force_rel_err']['value'] > \
        line['compared']['force_rel_err']['limit']


@pytest.mark.parametrize('cell', CELLS)
def test_altered_forces_are_not_correct(cell, monkeypatch):
    """An answer altered where it is produced."""
    from repro.kernels import ops
    real = ops.snap_force_pipeline

    def altered(*a, **kw):
        e, e_atom, f = real(*a, **kw)
        return e, e_atom, f * 1.001
    monkeypatch.setattr(ops, 'snap_force_pipeline', altered)
    correct, _ = run_cell(cell)
    assert not correct


def test_md_step_that_returns_its_state_unchanged(monkeypatch):
    from repro.md import integrate
    real = integrate.make_device_chunk_fn

    def frozen(*a, **kw):
        chunk = real(*a, **kw)

        def same(pos, vel, f, box, nbr_idx, shifts, mask, pos_ref, flags,
                 e_ref):
            out = chunk(pos, vel, f, box, nbr_idx, shifts, mask, pos_ref,
                        flags, e_ref)
            return (pos, vel, f, nbr_idx, shifts, mask, pos_ref) + out[7:]
        return same
    monkeypatch.setattr(integrate, 'make_device_chunk_fn', frozen)
    correct, line = run_cell('md_2j8_bcc16k')
    assert not correct
    assert line['compared']['integrator_rel_err']['value'] > 0.5


@pytest.mark.parametrize('cell', CELLS)
def test_one_lane_tile_altered_is_not_correct(cell, monkeypatch):
    """An answer altered on one block of consecutive atoms only, as a
    fault confined to one lane tile of a kernel would be: the sample holds
    an atom of every block."""
    from repro.kernels import ops
    real = ops.snap_force_pipeline
    lo, hi = SAMPLE_BLOCK, 2 * SAMPLE_BLOCK

    def one_tile(*a, **kw):
        e, e_atom, f = real(*a, **kw)
        return e, e_atom, f.at[lo:hi].multiply(1.1)
    monkeypatch.setattr(ops, 'snap_force_pipeline', one_tile)
    correct, line = run_cell(cell)
    assert not correct
    assert line['compared']['force_rel_err']['value'] > \
        line['compared']['force_rel_err']['limit']
