"""Device time per step of the instructions that hold more than one
phase (a weight-gradient matmul fused with its parameter's update, a
backward fusion that recomputes a forward activation): the part of
``fwd_ms``, ``bwd_ms`` and ``optimizer_ms`` that rests on the cost model's
split and not on the clock. Joined in ``benchmark/program_trace.py``."""
from benchmark import program_trace

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    out = program_trace.phases(summary, context)
    return None if out is None else 1e3 * out["cross_s"] / out["steps"]
