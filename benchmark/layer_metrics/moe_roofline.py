"""The routed expert layers' share of their roofline: the least time the
chip could take for the model flops and least HBM bytes of all expert
blocks of a step, forward + backward (``benchmark/nemotron_h_costs.py``:
router, shared expert, and the held experts on the slots the device
counters say were routed to them), over the device time of the regions
``RoutedMoE_<k>`` (``moe_ms_per_step``). The recomputed forward is in the
time and not in the flops, so the share cannot pass 100."""
from benchmark import nemotron_h_costs, region_time

LAYER = "model"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    ms = region_time.class_ms(summary, context, "RoutedMoE")
    cfg, traffic = context["config"], context["traffic"]
    if ms is None or "seq_len" not in traffic:
        return None
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    seen = region_time.moe_counters()
    slots = None if seen is None else \
        seen["moe.slots_routed_here"] / seen["moe.steps"] / tokens
    share, _ = nemotron_h_costs.kind_roofline_pct(
        cfg, "E", traffic["seq_len"], tokens, 1e-3 * ms, summary["peaks"],
        slots_here=slots)
    return share
