"""Device time of the attention layers of a ``smallthinker`` step, both
kinds: everything under the regions ``GroupedQueryAttention_<k>``
(``nn.GroupedQueryAttention`` without head norms: four ``Linear``s, on the
window layers ``F.rotary_embedding`` twice, the repeat of K and V to the
query heads, the flash kernels — ``flash_win_*`` on the window layers,
``flash_fwd`` / ``flash_bwd`` on the global ones), forward + backward with
the recomputed forward, over the traced steps
(``benchmark/region_time.py``). Other families' programs have the class
too (``gqa_attention_ms_per_step``, ``bd_attention_ms_per_step`` read it
there): nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "smallthinker":
        return None
    return region_time.class_ms(summary, context, "GroupedQueryAttention")
