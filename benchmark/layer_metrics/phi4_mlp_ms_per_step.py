"""Device time of the feed-forwards of a ``phi4_flash`` step: everything
under the regions ``GatedMLP_<k>`` (``nn.GatedMLP`` 2,560 -> 10,240 ->
2,560, one a block: 68 % of the configuration's matrices), forward +
backward with the recomputed forward, over the traced steps
(``benchmark/region_time.py``). Another family's cell: nothing here (its
dense layers have metrics of their own, or none)."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "phi4_flash":
        return None
    return region_time.class_ms(summary, context, "GatedMLP")
