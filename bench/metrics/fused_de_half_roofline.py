"""Share of its roofline of the fused dE kernel
(``kernels/snap_fused_de_half.py``): the dU recursion and the contraction
with Y (``counts.de_stage``) over the kernel's device time in the trace."""
import readers

UNIT = '%'
LAYER = 'kernel snap_fused_de_half'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'higher'
WORKLOADS = ['md_2j8_bcc16k', 'force_2j14_bcc2k']
# dE is the Mosaic kernel that gives one array, dE/dr as [K, 4, N]
# (see snap_u_roofline).
PATTERNS = [r'= f32\[[\d,]+\]\S* custom-call\('
            r'.*custom_call_target="tpu_custom_call"']


def read(ctx):
    return readers.kernel_roofline(ctx, PATTERNS, 'de')
