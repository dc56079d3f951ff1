"""The sliding-window flash kernels' share of their roofline in a
``phi4_flash`` step (40 heads x 8,192 under a window of 512 at 64 | 128 in
the cell): ``phi4_flash_costs.attention_kernel_costs("window_attention")``
— the flops of the pairs the window allows, 22 D a pair and head, each
operand's bytes once with K by key head and V by value pair, forward +
backward — over the device time of the kernels named ``flash_win_fwd`` /
``flash_win_bwd``. The kernels walk whole tiles where the count has the
allowed pairs (``phi4_win_flash_tiles_walked_pct``) and read K and V once a
QUERY head: both are in the share. A recomputed forward kernel would be in
the time and not in the flops, so the share cannot pass 100."""
from benchmark import phi4_flash_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "phi4_flash" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, "flash_win_")
    if ms is None:
        return None
    return phi4_flash_costs.flash_roofline_pct(
        cfg, traffic, ("window_attention",), 1e-3 * ms, summary["peaks"])
