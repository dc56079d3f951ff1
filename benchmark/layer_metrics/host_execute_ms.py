"""Median time of the program's span ``jit.execute`` in the traced steps
(host events of the profiler's trace): the call of the compiled
executable itself, until it returns its (not yet computed) results."""
from benchmark import program_trace

LAYER = "entry"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.span_ms(context, "jit.execute")
