"""One-off chip probe: the Y kernel's f32 feed at ``precision=HIGHEST``
against Mosaic's default dot precision.

    python benchmarks/y_precision_probe.py      # needs a TPU

For 2J=8 and 2J=14 on ``chip_smoke.py``'s inputs (the paper's 2000-atom
bcc W box, 26 neighbours, seeded beta) it compiles the f32 kernel force
call twice: as committed (``HIGHEST``), and with ``snap_y._precision``
patched to leave the precision to Mosaic.  Each variant prints its force
and energy error against the f64 jnp adjoint on the host CPU and the best
of three warm calls.  Times are one-off readings of one process, not
benchmark results.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402  (puts <repo>/src on sys.path)


def force_call(cfg, beta, disp, nbr_idx, mask):
    """Compiled f32 kernel force call; (compile_s, warm_s, e, f)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.snap import energy_forces

    f32 = np.float32
    args = (jnp.asarray(beta, f32), jnp.asarray(disp[..., 0], f32),
            jnp.asarray(disp[..., 1], f32), jnp.asarray(disp[..., 2], f32),
            jnp.asarray(nbr_idx), jnp.asarray(mask))
    fn = jax.jit(lambda b, dx, dy, dz, ni, m: energy_forces(
        cfg, b, 0.0, dx, dy, dz, ni, m, impl='kernel', interpret=False))
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    t_compile = time.perf_counter() - t0
    e, _, f = jax.block_until_ready(compiled(*args))
    warm = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        warm.append(time.perf_counter() - t0)
    return t_compile, min(warm), float(e), f


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    jax.config.update('jax_enable_x64', True)   # the f64 oracle
    chip_smoke.phase_device(1)

    from repro.kernels import snap_y
    highest = snap_y._precision
    for config in ('snap_2j8', 'snap_2j14'):
        spec, cfg, _, _, nbr_idx, mask, disp, beta = \
            chip_smoke.paper_problem(config)
        e_ref, f_ref = chip_smoke.oracle_forces(cfg, beta, disp, nbr_idx,
                                                mask)
        for label, prec in (('HIGHEST', highest),
                            ('default', lambda mxu_dtype: None)):
            snap_y._precision = prec
            try:
                t_c, t_w, e, f = force_call(cfg, beta, disp, nbr_idx, mask)
            finally:
                snap_y._precision = highest
            print(f'y_precision {spec["name"]} precision={label} '
                  f'rel_force_err={chip_smoke.rel_err(f, f_ref)!r} '
                  f'rel_energy_err={abs(e - e_ref) / abs(e_ref)!r} '
                  f'compile_s={t_c:.3f} best_warm_call_s={t_w:.6f} '
                  f'(one-off reading, not a benchmark)', flush=True)


if __name__ == '__main__':
    main()
