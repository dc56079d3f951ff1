"""Device time of the grouped-query attention layers per step: everything
under the regions ``GroupedQueryAttention_<k>`` (``nn.
GroupedQueryAttention``: the four projections, the repeat of K and V to
the query heads, the flash kernels), forward + backward with the
recomputed forward, over the traced steps (``benchmark/region_time.py``)."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "GroupedQueryAttention")
