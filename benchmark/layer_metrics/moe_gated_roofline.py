"""The gated routed expert layers' share of their roofline: the least time
the chip could take for the model flops and least HBM bytes of all expert
layers of a step (the main blocks' and the multi-token-prediction
module's), forward + backward (``benchmark/joyai_llm_flash_costs.py``:
router, gated shared expert, and the held gated experts on the slots the
device counters say were routed to them), over the device time of the
regions ``RoutedMoE_<k>`` (``moe_ms_per_step``). The recomputed forward is
in the time and not in the flops, so the share cannot pass 100.
``moe_roofline`` reads the ``nemotron_h`` pattern and ungated experts and
is not for this configuration."""
from benchmark import joyai_llm_flash_costs, region_time

LAYER = "model"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    ms = region_time.class_ms(summary, context, "RoutedMoE")
    cfg, traffic = context["config"], context["traffic"]
    if ms is None or "first_k_dense_replace" not in cfg \
            or "seq_len" not in traffic:
        return None
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    seen = region_time.moe_counters()
    slots = None if seen is None else \
        seen["moe.slots_routed_here"] / seen["moe.steps"] / tokens
    share, _ = joyai_llm_flash_costs.moe_roofline_pct(
        cfg, tokens, 1e-3 * ms, summary["peaks"], slots_here=slots)
    return share
