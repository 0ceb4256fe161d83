"""Share of the traced window in which no operation ran on the device,
in the cells that step atoms (MD and force calls)."""
import readers

UNIT = '%'
LAYER = 'device'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'lower'
WORKLOADS = ['md_2j8_bcc16k', 'force_2j14_bcc2k']


def read(ctx):
    return readers.idle_pct(ctx)
