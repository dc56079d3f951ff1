"""Device time of the layer-norm kernels per step: the own time of the
Pallas kernels named ``layer_norm_fwd`` and ``layer_norm_bwd`` (the
``name=`` of their ``pl.pallas_call``, read from the compiled step's HLO by
``monitor.profile.instruction_ledger``), over the traced steps. Nothing to
read where the step holds none."""
from benchmark import program_trace

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.kernel_ms(summary, context, "layer_norm_")
