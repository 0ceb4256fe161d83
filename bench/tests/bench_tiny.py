"""A cell's files cut to a size the CPU runs in seconds (2J=2, a 54-atom
box, one sampled atom in every 16), for tests that drive a whole run
without a chip."""

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

SAMPLE_BLOCK = 16


def tiny_files(cell):
    files = copy.deepcopy(harness.cell_files(cell))
    files['config']['snap']['twojmax'] = 2
    files['config']['ncoeff'] = 5
    files['config']['natoms'] = 54
    files['traffic'].update(sample_block=SAMPLE_BLOCK, trace_seconds=1)
    return files


def run_cell(cell, seed=2 ** 31 + 99, seconds=1.0):
    """(correct, result line) of one run on the CPU at the tiny size."""
    import io
    import json
    from contextlib import redirect_stdout

    import run as bench_run
    out = io.StringIO()
    with redirect_stdout(out):
        correct = bench_run.main(
            ['--workload', cell, '--seed', str(seed), '--seconds',
             str(seconds), '--trace', '0'],
            require_tpu=False, files=tiny_files(cell))
    return correct, json.loads(out.getvalue().strip().splitlines()[-1])
