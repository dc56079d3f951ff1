"""Device time of the Mamba-2 mixers per step: everything under the
regions ``Mamba2Mixer_<k>`` (``nn.Mamba2Mixer``: in_proj, the causal
convolution ``F.causal_conv1d``, the chunked scan ``F.ssd_scan``, the
gated norm, out_proj), forward + backward with the recomputed forward,
over the traced steps (``benchmark/region_time.py``)."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "Mamba2Mixer")
