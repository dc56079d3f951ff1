"""The gated short-convolution kernels' share of the HBM peak: the least
bytes of one forward and one backward call a conv layer
(``lfm2_costs.shortconv_kernel_bytes``: 4 x and 7 x ``B S C`` bfloat16
values, the op's three inputs and one output, then its four inputs and
three outputs) over the device time of the kernels named
``gated_conv_fwd`` / ``gated_conv_bwd``. The recomputed forward kernel is
in the time and not in the bytes, so the share cannot pass 100. The
kernels do 8 flops a value: the bound is bytes."""
from benchmark import lfm2_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "lfm2_moe" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, "gated_conv_")
    if ms is None:
        return None
    return lfm2_costs.shortconv_roofline_pct(cfg, traffic, 1e-3 * ms,
                                             summary["peaks"])
