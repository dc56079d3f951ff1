"""(token, choice) slots routed to the experts held here, per optimizer
step, all expert layers added up: the program's device counter
``moe.slots_routed_here`` (``nn.RoutedMoE.stats``, added to inside the
compiled step) over ``moe.steps`` / expert layers. A router that spreads
evenly gives tokens x top-k x held / experts a layer."""
from benchmark import region_time

LAYER = "ops"
UNIT = "count"
MOVES = "step_ms"


def read(summary, counters, context):
    seen = region_time.moe_counters()
    layers = context["config"].get("hybrid_override_pattern", "").count("E")
    if seen is None or not layers:
        return None
    return seen["moe.slots_routed_here"] * layers / seen["moe.steps"]
