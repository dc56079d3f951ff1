"""Device time of the sliding-window flash kernels per step: the own time
of the Pallas kernels named ``flash_win_fwd`` and ``flash_win_bwd`` (the
``name=`` of their ``pl.pallas_call`` under ``flash_attention(window=)``),
the recomputed forward included, over the traced steps. Nothing to read
where the step holds none.

Read by the kernels' names, not by phase: the recomputed forward shares
the forward's lowering and so carries its ``fwd`` label in the trace, which
is why this cell's accepted ``fwd_ms`` holds the six window layers'
recomputed forward kernels (about 50 ms a step) that ``bwd_ms`` holds in
the other cells."""
from benchmark import program_trace

LAYER = "ops"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.kernel_ms(summary, context, "flash_win_")
