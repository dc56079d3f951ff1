"""Device time of the attention layers of a block-diffusion step: everything
under the regions ``GroupedQueryAttention_<k>`` of an ``sdar_moe`` program
(``nn.GroupedQueryAttention`` with head norms, rotary positions and the
block structure: four ``Linear``s, the two head norms, ``F.rotary_embedding``
twice, the repeat of K and V to the query heads, the flash kernels over the
two copies' rows), forward + backward with the recomputed forward, over the
traced steps (``benchmark/region_time.py``). Another family's program has
the class too (``gqa_attention_ms_per_step`` reads it there): nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "sdar_moe":
        return None
    return region_time.class_ms(summary, context, "GroupedQueryAttention")
