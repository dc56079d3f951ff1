"""Device time of the routed expert layers per step: everything under the
regions ``RoutedMoE_<k>`` (``nn.RoutedMoE``: ``F.moe_route``, the grouped
products of ``F.moe_experts`` over the rows routed to each held expert
with their sort, gathers and scatters, the shared expert), forward +
backward with the recomputed forward, over the traced steps
(``benchmark/region_time.py``)."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "RoutedMoE")
