"""The causal flash kernels' share of their roofline in the GLOBAL layers
of a ``smallthinker`` step (16,384 x 128 causal, 28 heads in the cell):
``smallthinker_costs.attention_kernel_costs(windowed=0)`` of the layers
without a window over the device time of the kernels named ``flash_fwd``
and ``flash_bwd`` (the window layers' are ``flash_win_*`` and are not in
it). A recomputed forward kernel is in the time and not in the flops, so
the share cannot pass 100."""
from benchmark import program_trace, smallthinker_costs

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "smallthinker" or "seq_len" not in traffic:
        return None
    ms = [program_trace.kernel_ms(summary, context, name)
          for name in ("flash_fwd", "flash_bwd")]
    if None in ms:
        return None
    return smallthinker_costs.flash_roofline_pct(
        cfg, traffic, 0, 1e-3 * sum(ms), summary["peaks"])
