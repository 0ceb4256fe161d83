"""Share of the chip's peak of the whole force step: the algorithmic
operations of U, Y and dE (``counts.py``) for every force evaluation in
the traced window, over the window's length and the bf16 peak."""
import counts

UNIT = '%'
LAYER = 'force step: core/snap.energy_forces(impl=kernel)'
MOVES = 'katom_steps_per_s'
SOURCE = 'device_trace'
BETTER = 'higher'
WORKLOADS = ['md_2j8_bcc16k', 'force_2j14_bcc2k']


def read(ctx):
    c, tr = ctx['counters'], ctx['trace']
    flops = counts.force_flops(int(c['twojmax']), int(c['atoms']),
                               int(c['npairs'])) * float(c['force_evals'])
    return 100.0 * flops / (tr['window_s'] * tr['n_devices']
                            * ctx['peaks']['flops_per_s'])
