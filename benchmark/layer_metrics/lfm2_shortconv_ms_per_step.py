"""Device time of the gated short-convolution operators of an ``lfm2_moe``
step: everything under the regions ``GatedShortConv_<k>``
(``nn.GatedShortConv``: in_proj, ``F.gated_short_conv`` — the kernels
``gated_conv_fwd`` / ``gated_conv_bwd`` on the chip —, out_proj), forward +
backward with the recomputed forward, over the traced steps
(``benchmark/region_time.py``). A program without the class (the parent's,
another configuration's): nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "GatedShortConv")
