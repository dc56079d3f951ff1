"""How uneven the router's load on the held experts is: the fullest
expert's rows over the mean expert's rows, averaged over calls (device
counters ``moe.expert_load_max`` x experts held / ``moe.slots_routed_
here``). 1 is an even spread. Each expert pays for its own rows
(``moe_rows_computed_per_step``), so an uneven load costs this chip little;
an exchange between chips would wait for the fullest."""
from benchmark import region_time

LAYER = "ops"
UNIT = "ratio"
MOVES = "step_ms"


def read(summary, counters, context):
    seen = region_time.moe_counters()
    if seen is None or not seen["moe.slots_routed_here"]:
        return None
    return seen["moe.expert_load_max"] * \
        context["config"]["n_routed_experts"] / seen["moe.slots_routed_here"]
