"""Median time the call of the compiled step takes to return to the loop
(host clock, over every step of the measured window): tracing-free
dispatch through ``jit.to_static``, state collection included."""
from benchmark import stats

LAYER = "entry"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if not counters.get("dispatch_s"):
        return None
    return 1e3 * stats.percentile(counters["dispatch_s"], 50)
