"""Host milliseconds per chunk of the device loop spent outside the wait
on the chip: hook, dispatch, read-back of the thermo rows and the loop's
own bookkeeping (``named.host_ms_per_chunk`` over the program's spans of
the traced window's ``run_nve`` call, read in this process)."""
import named

UNIT = 'ms'
LAYER = 'MD loop: md/integrate.run_nve(loop=device)'
MOVES = 'katom_steps_per_s'
SOURCE = 'program_counter'
BETTER = 'lower'
WORKLOADS = ['md_2j8_bcc16k']


def read(ctx):
    try:
        from repro.runtime import trace
    except ImportError:     # a program that records no spans
        return None
    return named.host_ms_per_chunk(trace.snapshot())
