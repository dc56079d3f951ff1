"""Device time of the gated short-convolution kernels per step: the own
time of the Pallas kernels named ``gated_conv_fwd`` and ``gated_conv_bwd``
(the ``name=`` of the two ``pl.pallas_call``s of
``ops/pallas/causal_conv1d.py``'s second pair), the recomputed forward
included, over the traced steps. Nothing to read where the step holds
none."""
from benchmark import program_trace

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.kernel_ms(summary, context, "gated_conv_")
