"""The flash-attention kernels' share of their roofline at a grouped-query
causal shape: the least time the chip could take for the attention the
step's ``*`` blocks need (``nemotron_h_costs.attention_kernel_costs``:
causal flops at the configuration's own ``head_dim``, each K/V head's
bytes once, forward + backward) over the device time of the kernels named
``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` (``flash_ms_per_
step``). A recomputed forward kernel is in the time and not in the flops,
so the share cannot pass 100. ``flash_roofline`` reads BERT's geometry
(head size = hidden / heads, a key mask) and is not for this shape."""
from benchmark import kernel_costs, nemotron_h_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    ms = program_trace.kernel_ms(summary, context, "flash_")
    cfg, traffic = context["config"], context["traffic"]
    layers = cfg.get("hybrid_override_pattern", "").count("*")
    if ms is None or not layers or "seq_len" not in traffic:
        return None
    flops, nbytes = nemotron_h_costs.attention_kernel_costs(
        cfg, traffic["seq_len"], traffic["batch_per_chip"])
    share, _ = kernel_costs.roofline_share_pct(
        layers * flops, layers * nbytes, 1e-3 * ms, summary["peaks"])
    return share
