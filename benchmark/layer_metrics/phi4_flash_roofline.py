"""The causal flash kernels' share of their roofline in a ``phi4_flash``
step (the full attention layer and the cross attention layers: 40 heads x
8,192 causal at 64 | 128 in the cell, the cross layer over ANOTHER layer's
keys and values): ``phi4_flash_costs.attention_kernel_costs`` of those
layers over the device time of the kernels named ``flash_fwd`` and
``flash_bwd``. The backward kernel makes a score tile once (five products
a tile, PR 40) where the count has the algorithm's seven, the kernels walk
whole tiles where the count has the allowed pairs, and at a score width of
64 a product fills half the MXU's depth: all are in the share."""
from benchmark import phi4_flash_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "phi4_flash" or "seq_len" not in traffic:
        return None
    ms = [program_trace.kernel_ms(summary, context, name)
          for name in ("flash_fwd", "flash_bwd")]
    if None in ms:
        return None
    return phi4_flash_costs.flash_roofline_pct(
        cfg, traffic, ("full_attention", "cross_attention"), 1e-3 * sum(ms),
        summary["peaks"])
