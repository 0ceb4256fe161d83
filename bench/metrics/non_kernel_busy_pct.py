"""Share of the device's busy time spent outside the three Pallas kernels
(layout entry, Y coefficients, force assembly; in MD also the integrator
and the cell-list rebuild)."""
import devtrace

UNIT = '%'
LAYER = 'XLA glue around the kernels'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'lower'
WORKLOADS = ['md_2j8_bcc16k', 'force_2j14_bcc2k']
# every Mosaic (Pallas) kernel, by its custom-call target
KERNELS = [r'custom_call_target="tpu_custom_call"']


def read(ctx):
    tr = ctx['trace']
    kernel_s = devtrace.matching(tr, KERNELS)
    if kernel_s is None:
        return None
    return 100.0 * (tr['busy_s'] - kernel_s) / tr['busy_s']
