"""Device time of the attention operators of an ``lfm2_moe`` step:
everything under the regions ``GroupedQueryAttention_<k>``
(``nn.GroupedQueryAttention`` with head norms and rotary positions: four
``Linear``s, two ``RMSNorm``s, ``F.rotary_embedding`` twice, the repeat of
K and V to the query heads, the flash kernels ``flash_fwd`` /
``flash_bwd`` at head size 64), forward + backward with the recomputed
forward, over the traced steps (``benchmark/region_time.py``). Other
families' programs have the class too (``gqa_attention_ms_per_step``,
``bd_attention_ms_per_step``, ``st_attention_ms_per_step`` read it there):
nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "lfm2_moe":
        return None
    return region_time.class_ms(summary, context, "GroupedQueryAttention")
