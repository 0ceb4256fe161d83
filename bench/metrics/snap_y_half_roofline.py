"""Share of its roofline of the half-layout Y kernel
(``kernels/snap_y.py``): the adjoint Y's operations and bytes
(``counts.y_stage``) over the kernel's device time in the trace."""
import readers

UNIT = '%'
LAYER = 'kernel snap_y (half)'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'higher'
WORKLOADS = ['md_2j8_bcc16k', 'force_2j14_bcc2k']
# Y is the Mosaic kernel that gives two half planes [H, N] and takes
# planes of that shape (U's) among its operands (see snap_u_roofline).
PATTERNS = [r'= \(f32\[([\d,]+)\]\S*, f32\[\1\]\S*\) custom-call\('
            r'.* f32\[\1\]\S* %.*custom_call_target="tpu_custom_call"']


def read(ctx):
    return readers.kernel_roofline(ctx, PATTERNS, 'y')
