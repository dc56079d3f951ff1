"""Slots routed to a held expert that the layer did not compute, since
the process began (device counter ``moe.slots_dropped``: an expert's rows
over the rung of the ladder it was given, as ``F.moe_experts`` sees them
inside the step). 0 while the ladder's last rung holds every token; read
so that a later change to the buffers cannot drop in silence."""
from benchmark import region_time

LAYER = "ops"
UNIT = "count"
MOVES = "step_ms"


def read(summary, counters, context):
    seen = region_time.moe_counters()
    return None if seen is None else seen["moe.slots_dropped"]
