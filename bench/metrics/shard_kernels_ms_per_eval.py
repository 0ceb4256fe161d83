"""Device milliseconds per force evaluation, per chip, of the three named
SNAP kernels (U, Y and dE: ``snap_u*``, ``snap_y*``, ``snap_fused_de*``)
in the atom-sharded device loop, where each chip runs them on its own
quarter of the atoms; against ``md_2j8_bcc16k``'s three
``snap_*_ms_per_eval`` it reads the kernels' strong scaling."""
import named

UNIT = 'ms'
LAYER = 'kernels of the atom-sharded force step'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'lower'
WORKLOADS = ['md_2j8_bcc16k_4chip']
KERNELS = ('snap_u', 'snap_y', 'snap_fused_de')


def read(ctx):
    secs = [named.kernel_seconds(ctx['trace'], k) for k in KERNELS]
    if any(s is None for s in secs):
        return None
    return 1000.0 * sum(secs) / float(ctx['counters']['force_evals'])
