"""The causal flash kernels' share of their roofline in an ``lfm2_moe``
step (2 x 32 heads x 8,192 x 64 causal in the cell):
``lfm2_costs.attention_kernel_costs`` of the attention layers over the
device time of the kernels named ``flash_fwd`` and ``flash_bwd``. The
backward kernel makes a score tile once (five products a tile, PR 40)
where the count has the algorithm's seven, and the kernels walk whole
tiles where the count has the allowed pairs: both are in the share. At
head size 64 a product fills half the MXU's depth."""
from benchmark import lfm2_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "lfm2_moe" or "seq_len" not in traffic:
        return None
    ms = [program_trace.kernel_ms(summary, context, name)
          for name in ("flash_fwd", "flash_bwd")]
    if None in ms:
        return None
    return lfm2_costs.flash_roofline_pct(cfg, traffic, 1e-3 * sum(ms),
                                         summary["peaks"])
