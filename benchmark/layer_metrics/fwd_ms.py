"""Device time of the forward pass per step: the own time, in the run's
trace, of the instructions the program labelled forward (everything under
the compiled step's root scope that is neither under ``bwd`` nor under an
optimizer scope; scopes from ``nn.Layer.__call__`` and ``F.*``, armed by
``jit.to_static`` while it traces), over the traced steps. Joined in
``benchmark/program_trace.py``; an instruction that holds several phases
gives each its modelled share (``cross_phase_ms``)."""
from benchmark import program_trace

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.phase_ms(summary, context, "fwd")
