"""Device time of the routed expert layers of an ``lfm2_moe`` step:
everything under the regions ``RoutedMoE_<k>`` (``nn.RoutedMoE`` with the
sigmoid router and SiLU-gated experts 1,792 wide: ``F.moe_route``, the
grouped gated products of ``F.moe_experts`` over the rows routed to each
held expert, with their sort, gathers and the scatter-add kernel), forward
+ backward with the recomputed forward, over the traced steps
(``benchmark/region_time.py``). The time ``moe_ms_per_step`` reads in the
nemotron cell (PERF.md section 7 row 32)."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "lfm2_moe":
        return None
    return region_time.class_ms(summary, context, "RoutedMoE")
