"""Benchmark harness entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--paper]

Emits ``name,us_per_call,derived`` CSV rows.  --paper runs the full
2000-atom problem sizes (slow on CPU); default is a quick profile with the
same structure.
"""

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument('--paper', action='store_true',
                    help='full 2000-atom problem sizes')
    args = ap.parse_args()
    quick = not args.paper

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update('jax_enable_x64', True)

    print('name,us_per_call,derived')

    print('# -- paper Fig.1 / Sec VI-C: memory footprints (analytic) --')
    from . import b_memory
    b_memory.run(quick)

    print('# -- paper Table I / Fig.4: grind time + adjoint speedup --')
    from . import b_grind_time
    b_grind_time.run(quick)

    print('# -- paper Figs.2/3: stage progression --')
    from . import b_stage_progression
    b_stage_progression.run(quick)

    print('# -- MD grind time: full NVE driver, scan vs host loop --')
    from . import b_md_grind
    b_md_grind.run(quick)

    print('# -- paper Sec VI: Pallas kernel stages (interpret mode) --')
    from . import b_kernels
    b_kernels.run(quick)

    print('# -- LM dry-run roofline summary (if dry-run artifacts exist) --')
    try:
        from . import roofline
        text, summary = roofline.report(dryrun_dir='experiments/dryrun_v3')
        n_ok = len(summary)
        print(f'roofline_cells_analyzed,0.0,{n_ok}')
        for (arch, shape), a in sorted(
                summary.items(), key=lambda kv: kv[1]['roofline_fraction']
        )[:3]:
            print(f'roofline_worst_{arch}_{shape},0.0,'
                  f'{a["roofline_fraction"]:.3%}_{a["dominant"]}')
    except Exception as e:
        print(f'roofline_skipped,0.0,{type(e).__name__}')


if __name__ == '__main__':
    main()
