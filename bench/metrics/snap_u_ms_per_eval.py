"""Device milliseconds per force evaluation of the U kernel
(``kernels/snap_u.py``: ``snap_u_half``, or ``snap_u`` in the full layout),
found by the name the program gives it (``named.py``)."""
import named

UNIT = 'ms'
LAYER = 'kernel snap_u (half)'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'lower'
WORKLOADS = ['md_2j8_bcc16k', 'force_2j14_bcc2k']


def read(ctx):
    return named.kernel_ms_per_eval(ctx, 'snap_u')
