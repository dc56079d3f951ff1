"""Rows the held experts' products ran over, per optimizer step, all
expert layers added up, padding included: the program's device counter
``moe.rows_computed`` (each expert's rows padded up to the rung of
``ops.moe``'s ladder that holds them) over ``moe.steps`` / expert layers.
This, and not the slots routed, is what ``moe_ms_per_step`` follows; over
``moe_slots_per_step`` it is the padding's factor."""
from benchmark import region_time

LAYER = "ops"
UNIT = "count"
MOVES = "step_ms"


def read(summary, counters, context):
    seen = region_time.moe_counters()
    layers = context["config"].get("hybrid_override_pattern", "").count("E")
    if seen is None or not layers or "moe.rows_computed" not in seen:
        return None
    return seen["moe.rows_computed"] * layers / seen["moe.steps"]
