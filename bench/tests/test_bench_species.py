"""The two cells of the ``md_nve_domain`` driver on the CPU at a tiny size
(2J=2, 54 atoms): the multi-element reference against the program and
against finite differences of its own energies, whole runs of the W-Be
cell that are sound and that are broken underneath, the set-up's fixed
timed chunks, the driver against ``md_nve`` with one element, the
four-chip cell's shards on two host devices, and the readers of the cells' per-layer metrics, on hand-made
op texts and on short windows recorded on the chip
(``data/md_wbe_trace.json.gz``, the W-Be cell's last 0.25 s, and
``data/md_4chip_trace.json.gz``, the four-chip cell's last 0.04 s on
each of its four chips)."""

import copy
import io
import json
import os
import subprocess
import sys
from contextlib import redirect_stdout
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(Path(__file__).resolve().parent))

import bench_tiny  # noqa: E402
import harness  # noqa: E402
import inputs  # noqa: E402
import reference  # noqa: E402
import reference_species  # noqa: E402

WBE = 'md_wbe_2j8_bcc16k'
FOUR = 'md_2j8_bcc16k_4chip'
SEED = 2 ** 31 + 77


@pytest.fixture(autouse=True)
def _x64():
    import jax
    jax.config.update('jax_enable_x64', True)


def tiny_files(cell, **traffic):
    files = bench_tiny.tiny_files(cell)
    files['traffic'].update(traffic)
    return files


def run_cell(files, seed=SEED, seconds=1.0):
    """(correct, result line) of one run of ``files`` on the CPU."""
    import run as bench_run
    out = io.StringIO()
    with redirect_stdout(out):
        correct = bench_run.main(
            ['--workload', 'tiny', '--seed', str(seed), '--seconds',
             str(seconds), '--trace', '0'], require_tpu=False, files=files)
    return correct, json.loads(out.getvalue().strip().splitlines()[-1])


def driver():
    return harness.driver_for(dict(driver='md_nve_domain'))


def wbe_config(twojmax):
    config = copy.deepcopy(harness.cell_files(WBE)['config'])
    config['snap']['twojmax'] = twojmax
    return config


def alloy(seed, cells=3, sigma=0.05, fraction=0.3):
    pos, box = inputs.bcc(cells, 3.1652)
    pos = inputs.displaced(pos, box, sigma, inputs.stream(seed, 'pos'))
    rng = inputs.stream(seed, 'species')
    species = (rng.random(len(pos)) < fraction).astype(np.int32)
    return pos, box, species


# ---------------------------------------------------------------- reference

@pytest.mark.parametrize('twojmax', [2, 4])
def test_reference_species_matches_the_program_adjoint(twojmax):
    from repro.core.snap import energy_forces
    from repro.md.neighbor import brute_neighbors
    config = wbe_config(twojmax)
    cfg = driver().snap_config(config)
    pos, box, species = alloy(11)
    beta = np.random.default_rng(3).normal(size=(2, cfg.ncoeff)) * 5e-2
    beta0 = np.array([0.1, -0.3])
    ni, m, disp, _ = brute_neighbors(pos, box, cfg.rcut, 40)
    _, e_atom, f = energy_forces(cfg, beta, beta0, disp[..., 0],
                                 disp[..., 1], disp[..., 2], ni, m,
                                 impl='adjoint', species=species)
    atoms = np.array([0, 7, 30, 41])
    e_ref, f_ref = reference_species.forces_on(config, beta, beta0, pos, box,
                                               species, atoms)
    np.testing.assert_allclose(e_ref, np.asarray(e_atom)[atoms], rtol=1e-12)
    np.testing.assert_allclose(f_ref, np.asarray(f)[atoms], atol=1e-12)
    e_tot, f_all = reference_species.energy_and_forces(
        config, beta, beta0, pos, box, species)
    assert e_tot == pytest.approx(float(np.sum(e_atom)), rel=1e-12)
    np.testing.assert_allclose(f_all, np.asarray(f), atol=1e-12)


def test_reference_species_forces_are_minus_its_energy_gradient():
    """Central differences of the reference's own total energy: forces
    are -dE/dr to the differences' own error (h = 1e-5 Å, float64)."""
    config = wbe_config(2)
    pos, box, species = alloy(5)
    beta = np.random.default_rng(4).normal(size=(2, 5)) * 5e-2
    _, f = reference_species.energy_and_forces(config, beta, 0.0, pos, box,
                                               species)
    h = 1e-5
    for atom in (3, int(np.flatnonzero(species == 1)[0])):
        for k in range(3):
            plus, minus = pos.copy(), pos.copy()
            plus[atom, k] += h
            minus[atom, k] -= h
            ep, _ = reference_species.energy_and_forces(
                config, beta, 0.0, plus, box, species)
            em, _ = reference_species.energy_and_forces(
                config, beta, 0.0, minus, box, species)
            assert -(ep - em) / (2 * h) == pytest.approx(
                f[atom, k], abs=1e-7 * np.abs(f).max())


def test_reference_species_with_one_element_is_the_reference():
    """A table of one element of radius rcut / (2 rcutfac) and weight 1 is
    ``reference.py``'s single-element SNAP."""
    config = wbe_config(2)
    config['species'] = config['species'][:1]
    s = dict(config['snap'], rcut=4.8123)
    del s['rcutfac']
    pos, box, _ = alloy(8)
    beta = np.random.default_rng(5).normal(size=5) * 5e-2
    atoms = np.array([1, 20])
    e1, f1 = reference_species.forces_on(config, beta[None], 0.2, pos, box,
                                         np.zeros(len(pos), int), atoms)
    e0, f0 = reference.forces_on(s, beta, 0.2, pos, box, atoms)
    np.testing.assert_allclose(e1, e0, rtol=1e-13)
    np.testing.assert_allclose(f1, f0, atol=1e-13)


def test_reference_species_local_verlet_is_whole_box_verlet():
    """On a box whose neighbourhood is every atom, the local integration
    with per-atom masses is whole-box velocity Verlet."""
    config = wbe_config(2)
    pos, box, species = alloy(6)
    beta = np.random.default_rng(6).normal(size=(2, 5)) * 5e-3
    mass = np.array([183.84, 9.012182])[species]
    acc = inputs.ACC_CONV / mass[:, None]
    vel = driver().velocities(mass, 300.0, inputs.stream(6, 'v'))
    dt = 0.0005
    x, v = pos.copy(), vel.copy()
    _, f = reference_species.energy_and_forces(config, beta, 0.0, x, box,
                                               species)
    for _ in range(3):
        v += 0.5 * dt * acc * f
        x += dt * v
        _, f = reference_species.energy_and_forces(config, beta, 0.0, x,
                                                   box, species)
        v += 0.5 * dt * acc * f
    atoms = np.array([2, int(np.flatnonzero(species == 1)[0])])
    got = reference_species.verlet_local(config, beta, 0.0, pos, vel, box,
                                         species, atoms, 3, dt, acc[:, 0])
    np.testing.assert_allclose(got, x[atoms], atol=1e-12)


def test_two_layer_local_verlet_holds_where_one_layer_drifts():
    """On a box larger than the moving set, with Be atoms in the shell:
    the default two moving layers match whole-box velocity Verlet (the
    program's float64 adjoint, ten 0.5 fs steps) to 1e-8 of the
    displacement (3e-9 here), a hundred times closer than one layer
    (7e-7), whose shell's constant acceleration a light atom's jerk
    defeats."""
    from repro.core.snap import energy_forces
    from repro.md.neighbor import brute_neighbors
    config = wbe_config(2)
    cfg = driver().snap_config(config)
    pos, box, species = alloy(9, cells=7, sigma=0.05, fraction=0.2)
    beta = np.random.default_rng(9).normal(size=(2, 5)) * 5e-2
    mass = np.array([183.84, 9.012182])[species]
    acc = inputs.ACC_CONV / mass[:, None]
    vel = driver().velocities(mass, 600.0, inputs.stream(9, 'v'))

    def force(x):
        ni, m, d, _ = brute_neighbors(x, box, cfg.rcut, 40)
        return np.asarray(energy_forces(cfg, beta, 0.0, d[..., 0],
                                        d[..., 1], d[..., 2], ni, m,
                                        species=species)[2])
    dt, x, v = 0.0005, pos.copy(), vel.copy()
    f = force(x)
    for _ in range(10):
        v += 0.5 * dt * acc * f
        x += dt * v
        f = force(x)
        v += 0.5 * dt * acc * f
    atoms = np.array([0, int(np.flatnonzero(species == 1)[0])])
    moved = np.abs(x[atoms] - pos[atoms]).max()
    err = {layers: np.abs(reference_species.verlet_local(
        config, beta, 0.0, pos, vel, box, species, atoms, 10, dt,
        acc[:, 0], layers=layers) - x[atoms]).max() / moved
        for layers in (1, 2)}
    assert err[2] < 1e-8 and err[2] < err[1] / 100, err


# ---------------------------------------------------------------- whole runs

def test_wbe_cell_inputs():
    """20% of the sites Be from the mix's species_seed, both elements in
    every 128-atom block of the sample, two integrator atoms of each."""
    files = copy.deepcopy(harness.cell_files(WBE))
    run = harness.Run(name=WBE, seed=SEED, seconds=10.0, trace=False,
                      **files)
    species = driver().species_of(run, 16000)
    assert (species == 1).sum() == 3200 and (species == 0).sum() == 12800
    atoms = driver().sampled_atoms(run, species)
    assert len(atoms) == 250
    for lo in range(0, 16000, 128):
        block = atoms[(atoms >= lo) & (atoms < lo + 128)]
        assert sorted(species[block]) == [0, 1]
    run.seed = SEED + 1
    assert not np.array_equal(atoms, driver().sampled_atoms(run, species))


def test_wbe_sound_run_is_correct():
    correct, line = run_cell(tiny_files(WBE))
    assert correct and line['correct'] and line['failed'] == 0
    assert set(line['compared']) == {'force_rel_err', 'integrator_rel_err'}
    assert line['compared']['force_rel_err']['value'] < 1e-5


def _patched_pipeline(monkeypatch, change):
    from repro.kernels import ops
    real = ops.snap_force_pipeline

    def altered(*a, **kw):
        return change(real, *a, **kw)
    monkeypatch.setattr(ops, 'snap_force_pipeline', altered)


def test_wbe_altered_forces_are_not_correct(monkeypatch):
    def scaled(real, *a, **kw):
        e, e_atom, f = real(*a, **kw)
        return e, e_atom, f * 1.001
    _patched_pipeline(monkeypatch, scaled)
    correct, line = run_cell(tiny_files(WBE))
    assert not correct
    assert line['compared']['force_rel_err']['value'] > \
        line['compared']['force_rel_err']['limit']


def test_wbe_swapped_coefficients_are_not_correct(monkeypatch):
    """Be atoms given W's coefficients: the program's forces are then
    another potential's, and the reference's comparison finds it."""
    import jax.numpy as jnp

    def swapped(real, cfg, beta, *a, **kw):
        beta = jnp.asarray(beta)
        return real(cfg, beta.at[1].set(beta[0]), *a, **kw)
    _patched_pipeline(monkeypatch, swapped)
    correct, line = run_cell(tiny_files(WBE))
    assert not correct
    assert line['compared']['force_rel_err']['value'] > \
        line['compared']['force_rel_err']['limit']


def test_wbe_bf16_control_is_not_correct(monkeypatch):
    import jax.numpy as jnp

    def bf16(real, *a, **kw):
        kw['mxu_dtype'] = jnp.bfloat16
        return real(*a, **kw)
    _patched_pipeline(monkeypatch, bf16)
    correct, line = run_cell(tiny_files(WBE))
    assert not correct


def test_wbe_md_step_that_returns_its_state_unchanged(monkeypatch):
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
    correct, line = run_cell(tiny_files(WBE))
    assert not correct
    assert line['compared']['integrator_rel_err']['value'] > 0.5


@pytest.mark.parametrize('rebuilding', [False, True])
def test_wbe_window_starts_at_one_step_whatever_rebuilds(rebuilding):
    """With more than one element set-up times a fixed number of chunks,
    whether or not they rebuild the lists, so the window starts at the
    same step (and temperature) in every run; the fastest chunk sizes
    the window."""
    mod = driver()
    real = mod._run

    def chunk(st, run, n, tap=None):
        out = real(st, run, n, tap)
        st.cache['device_rebuilds'] = int(rebuilding)
        return out
    mod._run = chunk
    # the set-up's clock: the first call takes 1 s, the timed chunks
    # 0.9, 0.5, 0.7 and 0.6 s
    stamps = iter([0.0, 1.0, 1.0, 1.9, 1.9, 2.4, 2.4, 3.1, 3.1, 3.7])
    mod.time = SimpleNamespace(perf_counter=lambda: next(stamps))
    run = harness.Run(name=WBE, seed=SEED, seconds=1.0, trace=False,
                      **tiny_files(WBE))
    st = mod.setup(run)
    chunk_steps = int(run.traffic['log_every'])
    assert run.counters['setup_chunks'] == mod.TIMED_CHUNKS == 4
    assert st.state.step == chunk_steps * (1 + mod.TIMED_CHUNKS)
    assert st.chunk_s == pytest.approx(0.5)


def test_program_without_species_path_is_refused_at_once(monkeypatch):
    """A program without the species path (as before it existed) is
    refused in set-up, before anything compiles: no result line."""
    from repro.core.snap import SnapConfig
    monkeypatch.delattr(SnapConfig, 'species_path')
    files = tiny_files(WBE)
    run = harness.Run(name=WBE, seed=SEED, seconds=1.0, trace=False,
                      **files)
    with pytest.raises(SystemExit, match='no multi-element SNAP'):
        driver().setup(run)


def test_one_element_one_shard_is_md_nve():
    """With one element and one shard the driver's timed path is
    md_nve's: the same inputs, calls and comparisons, bit for bit."""
    out = {}
    for name in ('md_nve', 'md_nve_domain'):
        files = tiny_files(FOUR, shards=1)
        files['traffic']['driver'] = name
        mod = harness.driver_for(files['traffic'])
        run = harness.Run(name=FOUR, seed=SEED, seconds=1.0, trace=False,
                          **files)
        st = mod.setup(run)
        st.chunk_s = 1.0               # the same window length on both
        mod.measure(st, run, 2.0)
        mod.release(st)
        compared, attempted, failed = mod.check(st, run)
        out[name] = (st.carry, st.final_pos, compared, attempted, failed,
                     {k: run.counters[k] for k in ('steps', 'npairs',
                                                   'force_evals',
                                                   'rebuilds')})
    a, b = out['md_nve'], out['md_nve_domain']
    for k in a[0]:
        np.testing.assert_array_equal(a[0][k], b[0][k])
    np.testing.assert_array_equal(a[1], b[1])
    assert a[2:] == b[2:]


def test_four_chip_cell_shards_on_two_host_devices():
    """The four-chip cell's mix at ``shards: 2`` runs on two host CPU
    devices (a cell of two chips), compiles nothing in its window and
    comes out correct."""
    code = f'''
import sys
sys.path.insert(0, {str(BENCH)!r})
sys.path.insert(0, {str(BENCH / 'tests')!r})
import jax
jax.config.update('jax_enable_x64', True)
assert len(jax.devices()) == 2
from test_bench_species import run_cell, tiny_files
files = tiny_files({FOUR!r}, shards=2)
files['workload']['chips'] = 2
correct, line = run_cell(files)
assert correct and line['failed'] == 0, line
print('shards ok')
'''
    env = dict(os.environ)
    env['XLA_FLAGS'] = '--xla_force_host_platform_device_count=2'
    env['PYTHONPATH'] = str(BENCH.parent / 'src')
    p = subprocess.run([sys.executable, '-c', code], capture_output=True,
                       text=True, env=env, timeout=1200)
    assert p.returncode == 0 and 'shards ok' in p.stdout, p.stderr[-3000:]
    # the chunk program's compile for its own sharded outputs falls in
    # set-up, not in the window
    assert ' window_compiles=0' in p.stderr, p.stderr[-3000:]


# ---------------------------------------------------------------- readers

def _ctx(op_s, counters, n_devices=1):
    long_names = {k: '' for k in op_s}
    return dict(trace=dict(op_s=op_s, long_names=long_names,
                           n_devices=n_devices, busy_s=1.0, window_s=1.0),
                counters=counters, peaks=dict(flops_per_s=197e12,
                                              hbm_bytes_per_s=819e9))


def test_species_readers():
    import counts
    import counts_species
    mods = harness.metric_modules()
    pairs = [[300000, 100000], [100000, 40000]]
    c = dict(twojmax=8, atoms=16000, force_evals=61, species_pairs=pairs)
    op_s = {'%snap_u_species.3 = (f32[155,16000]...': 0.3,
            '%snap_y_species.3 = (f32[155,16000]...': 0.1,
            '%snap_de_species.3 = f32[72,4,16000]...': 0.6,
            '%snap_u_half.1 = ...': 5.0}
    ctx = _ctx(op_s, c)
    assert mods['species_u_ms_per_eval'].read(ctx) == pytest.approx(
        300.0 / 61)
    assert mods['species_de_ms_per_eval'].read(ctx) == pytest.approx(
        600.0 / 61)
    base = counts.de_stage(8, 16000, 540000)
    de = counts_species.stages(8, 16000, pairs)['de']
    assert de.flops == base.flops and de.bytes == base.bytes + 540000 * 4
    t_min = max(de.flops / 197e12, de.bytes / 819e9) * 61
    assert mods['species_de_roofline'].read(ctx) == pytest.approx(
        100 * t_min / 0.6)
    for k in ('u', 'y', 'de'):
        assert 0 < mods[f'species_{k}_roofline'].read(ctx) < 100
    # a program with no species kernels or counter reads nothing
    assert mods['species_y_ms_per_eval'].read(
        _ctx({'%snap_y_half.1 = ...': 1.0}, c)) is None
    assert mods['species_u_roofline'].read(
        _ctx(op_s, dict(c, species_pairs=None))) is None


def test_sharded_readers():
    mods = harness.metric_modules()
    # op texts as a 4-chip trace gives them: an op that copies a
    # collective's result names it only among its operands
    op_s = {'%reduce_scatter.6 = f32[4000,3]{1,0} reduce-scatter('
            'f32[16000,3]{1,0} %fusion.1), channel_id=1': 0.02,
            '%all-reduce.21 = (f32[]{:T(128)}, f32[4]{0}) all-reduce('
            'f32[]{:T(128)} %fusion.278)': 0.01,
            '%all-gather-start.2 = (f32[1,4000,3], f32[4,4000,3]) '
            'all-gather-start(f32[1,4000,3] %bitcast.7)': 0.004,
            '%copy.416 = f32[4,4000,3]{2,1,0} copy(f32[4,4000,3]{1,2,0} '
            '%all-gather.38)': 0.5,
            '%snap_u_half.1 = ...': 0.3, '%snap_y_half.1 = ...': 0.1,
            '%snap_fused_de_half.1 = ...': 0.6, '%fusion.3 = ...': 0.5}
    ctx = _ctx(op_s, dict(force_evals=100), n_devices=4)
    assert mods['md_collective_ms_per_eval'].read(ctx) == pytest.approx(
        0.34)
    assert mods['shard_kernels_ms_per_eval'].read(ctx) == pytest.approx(10.0)
    assert mods['md_collective_ms_per_eval'].read(
        _ctx({'%fusion.3 = ...': 0.5}, dict(force_evals=100))) is None


def test_readers_on_recorded_traces():
    """The readers find the species kernels and the four chips'
    collectives and kernels by the names and op kinds a chip's trace gives
    them (counts per evaluation are the windows' own: about 4 in each)."""
    import devtrace
    mods = harness.metric_modules()
    data = Path(__file__).resolve().parent / 'data'
    wbe = devtrace.reduce(devtrace.load_saved(data / 'md_wbe_trace.json.gz'))
    c = dict(twojmax=8, atoms=16000, force_evals=4,
             species_pairs=[[263526, 55204], [55204, 15556]])
    ctx = dict(trace=wbe, counters=c, peaks=harness.peaks_for(
        'TPU v5 lite'))
    for k in ('u', 'y', 'de'):
        assert mods[f'species_{k}_ms_per_eval'].read(ctx) > 0.5
        assert 0 < mods[f'species_{k}_roofline'].read(ctx) < 100
    four = devtrace.reduce(devtrace.load_saved(
        data / 'md_4chip_trace.json.gz'))
    assert four['n_devices'] == 4
    ctx = dict(trace=four, counters=dict(force_evals=4))
    assert 0 < mods['md_collective_ms_per_eval'].read(ctx) < 1
    assert 3 < mods['shard_kernels_ms_per_eval'].read(ctx) < 6
