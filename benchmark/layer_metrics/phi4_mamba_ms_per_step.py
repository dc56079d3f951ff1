"""Device time of the Mamba-1 mixers of a ``phi4_flash`` step: everything
under the regions ``MambaMixer_<k>`` (``nn.MambaMixer``: in_proj, the causal
convolution — the kernels ``conv1d_fwd`` / ``conv1d_bwd`` on the chip —,
x_proj, dt_proj, ``F.selective_scan`` with its relayouts, soft-plus and
gate, out_proj), forward + backward with the recomputed forward, over the
traced steps (``benchmark/region_time.py``). ``sscan_ms_per_step`` is the
scan kernels' part of it. A program without the class (the parent's,
another configuration's): nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context, "MambaMixer")
