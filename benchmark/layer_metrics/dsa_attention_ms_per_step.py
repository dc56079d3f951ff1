"""Device time of the sparse attention layers of a ``keye_vl`` step:
everything under the regions ``SparseGroupedQueryAttention_<k>``
(``nn.SparseGroupedQueryAttention``: the four projections, ``F.qk_heads``
twice, the indexer's three projections, its layer norm and rotations,
``F.dsa_select``, the flash kernels under the selection,
``F.dsa_indexer_loss``), forward + backward with the recomputed forward,
over the traced steps (``benchmark/region_time.py``). A program without
the class (the parent's, another configuration's): nothing here."""
from benchmark import region_time

LAYER = "model"
UNIT = "ms"
MOVES = "step_ms"


def read(summary, counters, context):
    return region_time.class_ms(summary, context,
                                "SparseGroupedQueryAttention")
