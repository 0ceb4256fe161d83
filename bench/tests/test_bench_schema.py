"""The benchmark's files against its contract: BENCHMARK.json, the cell,
configuration, mix and metric files it names, the result line, and the
refusal to run without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import run as bench_run  # noqa: E402

NAME = re.compile(r'^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$')
UNIT = re.compile(r'^[A-Za-z0-9_/%.-]{1,16}$')


@pytest.fixture(scope='module')
def bench_json():
    return json.loads((ROOT / 'BENCHMARK.json').read_text())


def test_top_level_keys(bench_json):
    assert set(bench_json) == {'command', 'paths', 'run_seconds', 'configs',
                               'workloads', 'end_to_end', 'per_layer'}
    assert bench_json['command'] == ['python3', 'bench/run.py']
    assert bench_json['paths'] == ['bench']
    assert 1 <= bench_json['run_seconds'] <= 51


def test_cells_and_configs_are_files(bench_json):
    configs = {c['name']: c for c in bench_json['configs']}
    for c in configs.values():
        assert set(c) == {'name', 'source', 'file', 'reduced', 'why'}
        assert c['file'] == f'bench/configs/{c["name"]}.json'
        data = json.loads((ROOT / c['file']).read_text())
        assert data['reduced'] == c['reduced']
        assert set(data['reduced']) <= set(data) - {'reduced'}
        assert data['ncoeff'] == len(__import__('snapidx').bispectrum_triples(
            data['snap']['twojmax']))
    used = set()
    for w in bench_json['workloads']:
        assert set(w) == {'name', 'config', 'traffic', 'chips', 'why'}
        assert NAME.match(w['name']) and NAME.match(w['traffic'])
        assert len(w['why']) <= 200 and w['chips'] in (1, 4)
        files = harness.cell_files(w['name'])
        assert {k: files['workload'][k] for k in
                ('config', 'traffic', 'chips', 'why')} == \
            {k: w[k] for k in ('config', 'traffic', 'chips', 'why')}
        assert (BENCH / 'traffic' / f'{files["traffic"]["driver"]}.py'
                ).exists()
        used.add(w['config'])
    assert used == set(configs)


def test_metrics_match_their_files(bench_json):
    cells = {w['name'] for w in bench_json['workloads']}
    e2e = {m['name']: m for m in bench_json['end_to_end']}
    assert 'setup_s' in e2e and e2e['setup_s']['bound'] <= 0.25
    for m in e2e.values():
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert m['source'] in ('host_clock', 'device_trace')
        assert 0.01 <= m['bound'] <= 0.25
    mods = harness.metric_modules()
    assert {m['name'] for m in bench_json['per_layer']} == set(mods)
    for m in bench_json['per_layer']:
        mod = mods[m['name']]
        assert NAME.match(m['name']) and UNIT.match(m['unit'])
        assert (m['unit'], m['better'], m['source'], m['layer'], m['moves'],
                m['workloads']) == (mod.UNIT, mod.BETTER, mod.SOURCE,
                                    mod.LAYER, mod.MOVES, mod.WORKLOADS)
        assert set(m['workloads']) <= cells
        moved = e2e[m['moves']]
        assert set(m['workloads']) <= set(moved.get('workloads', cells))
    for cell in cells:
        reported = [m for m in bench_json['per_layer']
                    if cell in m['workloads']]
        assert reported, f'{cell} reports no per-layer metric'


def test_a_new_metric_file_is_picked_up(tmp_path):
    shutil.copytree(BENCH / 'metrics', tmp_path / 'metrics')
    (tmp_path / 'metrics' / 'extra_count.py').write_text(
        "UNIT = '1'\nLAYER = 'device'\nMOVES = 'katom_steps_per_s'\n"
        "SOURCE = 'program_counter'\nBETTER = 'higher'\n"
        "WORKLOADS = ['force_2j14_bcc2k']\n\n\n"
        "def read(ctx):\n    return float(ctx['counters']['calls'])\n")
    (tmp_path / 'metrics' / 'silent.py').write_text(
        "UNIT = '%'\nLAYER = 'device'\nMOVES = 'katom_steps_per_s'\n"
        "SOURCE = 'device_trace'\nBETTER = 'higher'\n"
        "WORKLOADS = ['force_2j14_bcc2k']\n\n\n"
        "def read(ctx):\n    return None\n")
    for p in (tmp_path / 'metrics').glob('*.py'):
        if p.name not in ('extra_count.py', 'silent.py'):
            p.unlink()
    run = harness.Run(name='force_2j14_bcc2k', seed=1, seconds=1.0,
                      trace=True, counters=dict(calls=3),
                      **harness.cell_files('force_2j14_bcc2k'))
    out = bench_run.per_layer(run, {}, {}, {}, 1.0, bench=tmp_path)
    assert out == {'extra_count': dict(value=3.0, unit='1')}


def test_result_line_schema():
    line = harness.result_line(
        True, 10, 0, {'setup_s': dict(value=1.5, unit='s')},
        dict(platform='tpu', kind='TPU v5 lite', count=1,
             memory_peak_bytes=7), [('force_rel_err', 1e-6, 1e-4)],
        breakdown=dict(device_ops=[['a', 1.0]], idle_gaps=[['b', 0.5]]))
    out = json.loads(line)
    assert list(out) == ['correct', 'attempted', 'failed', 'metrics',
                         'device', 'breakdown', 'compared']
    assert out['compared'] == {'force_rel_err': dict(value=1e-6,
                                                     limit=1e-4)}


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS='cpu', **(env_extra or {}))
    return subprocess.run(
        [sys.executable, 'bench/run.py', '--workload', 'force_2j14_bcc2k',
         '--seed', '1', '--seconds', '1', '--trace', '0'],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert 'needs a TPU' in p.stderr
    assert '"correct"' not in p.stdout


def test_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / 'bench',
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(ROOT / 'BENCHMARK.json', tmp_path)
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
