"""Device milliseconds per force evaluation of the species path's
``snap_de_species`` kernel (``kernels/snap_fused_de_half.py``), found by
the name the program gives it (``named.py``)."""
import named

UNIT = 'ms'
LAYER = 'kernel snap_de_species'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'lower'
WORKLOADS = ['md_wbe_2j8_bcc16k']


def read(ctx):
    return named.kernel_ms_per_eval(ctx, 'snap_de_species')
