"""Device milliseconds per force evaluation in collective operations of
the atom-sharded device loop, the mean over the chips: the ops whose
kind (the HLO opcode after the result type, with its ``-start`` and
``-done`` halves) is reduce-scatter, all-reduce, all-gather,
collective-permute or all-to-all.  They are the force assembly's
``psum_scatter``, the gather of the sharded forces and the energy's
``psum`` (``core/snap.assemble_forces``,
``kernels/ops.make_sharded_force_fn``); an op that only reads a
collective's result (a copy of it) is not one."""
import devtrace

UNIT = 'ms'
LAYER = 'collectives of the atom-sharded force step'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'lower'
WORKLOADS = ['md_2j8_bcc16k_4chip']
PATTERNS = [r'^%\S+ = .*?\b(reduce-scatter|all-reduce|all-gather'
            r'|collective-permute|all-to-all)(-start|-done)?\(']


def read(ctx):
    sec = devtrace.matching(ctx['trace'], PATTERNS)
    if sec is None:
        return None
    return 1000.0 * sec / float(ctx['counters']['force_evals'])
