"""Device time of the differential attention layers of a ``phi4_flash``
step: everything under the regions ``DifferentialAttention_<k>``
(``nn.DifferentialAttention``: the projections with their biases, the
pairing of heads — K and V repeated to a head a query head —, the flash
kernels at 64 | 128 under the window, full causal and over another layer's
keys and values, ``F.differential_heads`` — lambda, the subtraction, the
pair norm, in float32 —, o_proj), forward + backward with the recomputed
forward, over the traced steps (``benchmark/region_time.py``). A program
without the class: nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "DifferentialAttention")
