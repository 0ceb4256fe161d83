"""Share of its roofline of the fused dE kernel of the species path
(``kernels/snap_fused_de_half.py``, ``snap_de_species``): the dU
recursion and the contraction with Y (``counts_species``) over the
kernel's device time in the trace, found by the name the program gives
it (``named.py``)."""
import counts_species

UNIT = '%'
LAYER = 'kernel snap_de_species'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'higher'
WORKLOADS = ['md_wbe_2j8_bcc16k']


def read(ctx):
    return counts_species.kernel_roofline(ctx, 'snap_de_species', 'de')
