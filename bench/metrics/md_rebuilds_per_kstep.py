"""Neighbour rebuilds of the device loop per 1000 steps
(``run_nve``'s ``fn_cache['device_rebuilds']`` over the traced window)."""

UNIT = '1/kstep'
LAYER = 'MD loop: md/integrate.run_nve(loop=device)'
MOVES = 'katom_steps_per_s'
SOURCE = 'program_counter'
BETTER = 'lower'
WORKLOADS = ['md_2j8_bcc16k']


def read(ctx):
    c = ctx['counters']
    return 1000.0 * c['rebuilds'] / c['steps']
