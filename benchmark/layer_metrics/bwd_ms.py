"""Device time of the backward pass per step: the own time of the
instructions under the scope ``bwd``, which ``autograd.backward`` runs the
tape's sweep under (each node's layer scopes re-entered inside it), over
the traced steps. Joined in ``benchmark/program_trace.py``."""
from benchmark import program_trace

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.phase_ms(summary, context, "bwd")
