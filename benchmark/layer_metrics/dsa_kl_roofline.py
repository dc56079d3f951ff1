"""The share of its roofline of the kernel of the indexer's loss: the least
time the chip could take for the loss's one pass over the SELECTED pairs of
the step's layers (``keye_vl_costs.kl_kernel_costs``: the heads' scores
again, the index scores again and their two backward products) over the
device time of the kernels whose name starts with ``dsa_kl``. The pass
walks every causal tile, so the share reads about the selected share of
what it would dense. Nothing to read while the op runs its XLA route
(``dsa.kl.xla_traced``)."""
from benchmark import keye_vl_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "keye_vl" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, "dsa_kl")
    if ms is None:
        return None
    return keye_vl_costs.roofline_pct(keye_vl_costs.kl_kernel_costs, cfg, traffic,
                                      1e-3 * ms, summary["peaks"])
