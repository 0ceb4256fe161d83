"""The benchmark's harness: finds a cell's files by name, holds a run's
state, and builds the result line.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own under ``bench/``:

- ``workloads/<cell>.json``: ``config``, ``traffic``, ``chips``, ``why``;
- ``configs/<config>.json``: the SNAP configuration as it is run;
- ``traffic/<mix>.json``: the mix's parameters and its ``driver``;
- ``traffic/<driver>.py``: one general driver per kind of traffic;
- ``metrics/<metric>.py``: one reader per per-layer metric.

No file here names a cell, a mix or a metric.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        'bench_' + path.stem.replace('.', '_'), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def cell_files(name: str, bench: Path = BENCH) -> dict:
    """The cell's workload, configuration and traffic mix, by name."""
    workload = load_json(bench / 'workloads' / f'{name}.json')
    config = load_json(bench / 'configs' / f'{workload["config"]}.json')
    traffic = load_json(bench / 'traffic' / f'{workload["traffic"]}.json')
    return dict(workload=workload, config=config, traffic=traffic)


def driver_for(traffic: dict, bench: Path = BENCH):
    return load_module(bench / 'traffic' / f'{traffic["driver"]}.py')


def metric_modules(bench: Path = BENCH) -> dict:
    """Every per-layer metric reader under ``metrics/``, by file name."""
    return {p.name[:-3]: load_module(p)
            for p in sorted((bench / 'metrics').glob('*.py'))}


def process_start_time() -> float:
    """Wall-clock time at which this process started (Linux /proc)."""
    try:
        with open('/proc/self/stat') as f:
            fields = f.read().rsplit(')', 1)[1].split()
        start_ticks = int(fields[19])
        with open('/proc/uptime') as f:
            uptime = float(f.read().split()[0])
        hz = os.sysconf('SC_CLK_TCK')
        return time.time() - (uptime - start_ticks / hz)
    except (OSError, ValueError, IndexError):
        return time.time()


@dataclass
class Run:
    """One run of one cell: its files, seed, and what it has measured."""
    name: str
    seed: int
    seconds: float
    trace: bool
    workload: dict
    config: dict
    traffic: dict
    counters: dict = field(default_factory=dict)

    @property
    def snap(self) -> dict:
        return self.config['snap']

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around a call into the program.  In a traced run
        it is also a ``TraceAnnotation`` on the profiler's clock, so the
        trace reduction can say what the host did in each device gap."""
        if not self.trace:
            yield
            return
        import jax
        with jax.profiler.TraceAnnotation(f'bench.{name}'):
            yield


def compile_counter():
    """A counter of XLA backend compiles, for the 'nothing compiles in the
    window' check."""
    import jax
    box = dict(n=0)

    def listener(event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            box['n'] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return box


def device_info(chips: int, require_tpu: bool) -> dict:
    """The device as JAX reports it; exits nonzero off a TPU or with
    fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != 'tpu':
        raise SystemExit(f'bench: needs a TPU, but JAX found platform '
                         f'{platform!r}; no result')
    if len(devs) < chips:
        raise SystemExit(f'bench: the cell needs {chips} chips, JAX found '
                         f'{len(devs)}; no result')
    return dict(platform=platform, kind=devs[0].device_kind,
                count=len(devs))


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip (0 where not reported)."""
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get('peak_bytes_in_use', 0)))
    return max(peaks) if peaks else 0


def peaks_for(kind: str, bench: Path = BENCH) -> dict:
    table = load_json(bench / 'peaks.json')['devices']
    if kind not in table:
        raise KeyError(f'device kind {kind!r} is not in peaks.json')
    return table[kind]


def result_line(correct, attempted, failed, metrics, device, compared,
                breakdown=None) -> str:
    """The last line of stdout; ``compared`` comes last."""
    out = dict(correct=bool(correct), attempted=int(attempted),
               failed=int(failed), metrics=metrics, device=device)
    if breakdown is not None:
        out['breakdown'] = breakdown
    out['compared'] = {n: dict(value=v, limit=lim) for n, v, lim in compared}
    return json.dumps(out)
