"""Device time of the multi-head latent attention layers per step:
everything under the regions ``MultiHeadLatentAttention_<k>`` (``nn.
MultiHeadLatentAttention``: the five projections and two latent norms,
``F.rotary_embedding`` of the query heads and of the shared key head, the
repeat of that key head to every head, the concatenations, the flash
kernels), forward + backward with the recomputed forward, over the traced
steps (``benchmark/region_time.py``). The multi-token-prediction module's
attention is one of the instances and is in ``mtp_ms_per_step`` too."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "MultiHeadLatentAttention")
