"""Share of its roofline of the U kernel (``kernels/snap_u.py``, half
layout): the U stage's operations and bytes (``counts.u_stage``) over the
kernel's device time in the trace."""
import readers

UNIT = '%'
LAYER = 'kernel snap_u (half)'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'higher'
WORKLOADS = ['md_2j8_bcc16k', 'force_2j14_bcc2k']
# The program names no kernel, so the profiler's XLA Ops line shows the
# HLO instruction: U is the Mosaic kernel that takes the packed
# displacements alone, [K, 4, N], and gives the two half planes [H, N].
PATTERNS = [r'= \(f32\[([\d,]+)\]\S*, f32\[\1\]\S*\) custom-call\('
            r'f32\[[\d,]+\]\S* %[\w.-]+\), '
            r'custom_call_target="tpu_custom_call"']


def read(ctx):
    return readers.kernel_roofline(ctx, PATTERNS, 'u')
