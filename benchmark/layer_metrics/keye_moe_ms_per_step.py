"""Device time of the routed expert layers of a ``keye_vl`` step:
everything under the regions ``RoutedMoE_<k>`` (``nn.RoutedMoE`` with the
soft-max router and no shared expert, the ``sdar_moe`` layer:
``F.moe_route``, the grouped gated products of ``F.moe_experts`` over the
rows routed to each held expert), forward + backward with the recomputed
forward, over the traced steps (``benchmark/region_time.py``)."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "keye_vl":
        return None
    return region_time.class_ms(summary, context, "RoutedMoE")
