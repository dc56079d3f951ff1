"""Rows the held experts' products ran over for each row routed to them in
an ``lfm2_moe`` run: the program's device counters ``moe.rows_computed``
(each expert's rows padded up to the rung of ``ops.moe``'s ladder that
holds them) over ``moe.slots_routed_here``, every expert layer and every
step since the process began. 1 is no padding. The cell's even share, 2,048
rows an expert, is a rung of the op's ladder, where an expert that draws
one row more runs 4,096: about 1.5 at a run's start (half the held
expert-layers over the rung), 1.6 at its end (all of them on 4,096, their
load a quarter over the share). The same expression as
``moe_padding_factor``, whose list an accepted test holds to the joyai
cell alone (PERF.md section 7 row 32)."""
from benchmark import region_time

LAYER = "ops"
UNIT = "ratio"
MOVES = "step_ms"


def read(summary, counters, context):
    if context["config"].get("family") != "lfm2_moe":
        return None
    seen = region_time.moe_counters()
    if seen is None or not seen.get("moe.slots_routed_here") \
            or "moe.rows_computed" not in seen:
        return None
    return seen["moe.rows_computed"] / seen["moe.slots_routed_here"]
