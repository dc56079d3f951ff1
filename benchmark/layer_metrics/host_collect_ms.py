"""Median time of the program's span ``jit.collect`` in the traced steps
(host events of the profiler's trace): what ``StaticFunction.__call__``
spends before it calls the executable — resolving its objects, the arena
flush, the cached state map, flattening the arguments, the cache key, the
list of state arrays."""
from benchmark import program_trace

LAYER = "entry"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return program_trace.span_ms(context, "jit.collect")
