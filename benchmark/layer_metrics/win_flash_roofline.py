"""The sliding-window flash kernels' share of their roofline: the least
time the chip could take for the attention that the step's window layers
need (``smallthinker_costs.attention_kernel_costs(windowed=1)``: the flops
of the pairs the window allows, each operand's bytes once with K and V by
key/value head, forward + backward) over the device time of the kernels
named ``flash_win_fwd`` / ``flash_win_bwd`` (``win_flash_ms_per_step``). A
recomputed forward kernel is in the time and not in the flops, so the share
cannot pass 100."""
from benchmark import program_trace, smallthinker_costs

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "smallthinker" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, "flash_win_")
    if ms is None:
        return None
    return smallthinker_costs.flash_roofline_pct(cfg, traffic, 1, 1e-3 * ms,
                                                 summary["peaks"])
