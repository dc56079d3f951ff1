"""Device time of the gated memory units of a ``phi4_flash`` step:
everything under the regions ``GatedMemoryUnit_<k>``
(``nn.GatedMemoryUnit``: in_proj, the SiLU gate on ANOTHER layer's scan
memory, out_proj), forward + backward with the recomputed forward, over the
traced steps (``benchmark/region_time.py``). A program without the class:
nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "GatedMemoryUnit")
