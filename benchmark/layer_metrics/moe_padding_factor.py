"""Rows the held experts' products ran over for each row routed to them:
the program's device counters ``moe.rows_computed`` (each expert's rows
padded up to the rung of ``ops.moe``'s ladder that holds them) over
``moe.slots_routed_here``, every expert layer and every step since the
process began. 1 is no padding; an expert that draws half of the first
rung's ``MIN_ROWS`` reads 2."""
from benchmark import region_time

LAYER = "ops"
UNIT = "ratio"
MOVES = "step_ms"


def read(summary, counters, context):
    seen = region_time.moe_counters()
    if seen is None or not seen.get("moe.slots_routed_here") \
            or "moe.rows_computed" not in seen:
        return None
    return seen["moe.rows_computed"] / seen["moe.slots_routed_here"]
