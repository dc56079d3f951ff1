"""Device time of the selective-scan kernels of a step: the Pallas kernels
named ``selective_scan_fwd`` / ``selective_scan_bwd``
(``ops/pallas/selective_scan.py``: Mamba-1's recurrence position by
position on the vector unit, the state in registers), own time over the
traced steps, a recomputed forward included. THE number to watch of these
kernels: their bound is the vector and transcendental units, which
``sscan_roofline`` cannot divide by. Nothing to read where the step holds
none."""
from benchmark import program_trace

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.kernel_ms(summary, context, "selective_scan_")
