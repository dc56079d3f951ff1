"""Device time of the flash-attention kernels under a selection per step:
the own time of the Pallas kernels named ``flash_sel_fwd`` and
``flash_sel_bwd`` (the ``name=`` of their ``pl.pallas_call``), over the
traced steps. Nothing to read where the step holds none."""
from benchmark import program_trace

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.kernel_ms(summary, context, "flash_sel_")
