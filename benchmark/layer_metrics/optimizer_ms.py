"""Device time of the optimizer's update per step: the own time of the
instructions under ``opt.<Cls>`` (``Optimizer._apply_update``) or
``arena.pack`` (the flat arena's gradient pack), over the traced steps.
Joined in ``benchmark/program_trace.py``. Where the update is fused with a
weight-gradient matmul this is its modelled share of that fusion, and the
fusion's whole time is in ``cross_phase_ms``."""
from benchmark import program_trace

LAYER = "optimizer"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.phase_ms(summary, context, "opt")
