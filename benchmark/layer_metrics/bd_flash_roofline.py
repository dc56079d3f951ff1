"""The flash-attention kernels' share of their roofline under the
block-diffusion structure: the least time the chip could take for the
attention that the step's blocks need (``sdar_moe_costs.
attention_kernel_costs``: the flops of the allowed pairs alone, over the
two copies' rows, each operand's bytes once with K and V by key/value
head, forward + backward) over the device time of the kernels whose name
starts with ``flash_`` (``flash_ms_per_step``; under the structure the
calls are named ``flash_bd_fwd``, ``flash_bd_bwd_dq``, ``flash_bd_bwd_dkv``). A recomputed forward kernel is in the time and not in the flops,
so the share cannot pass 100."""
from benchmark import kernel_costs, program_trace, sdar_moe_costs

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "sdar_moe" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, "flash_")
    if ms is None:
        return None
    layers = cfg["num_hidden_layers"]
    flops, nbytes = sdar_moe_costs.attention_kernel_costs(
        cfg, traffic["seq_len"], traffic["batch_per_chip"])
    share, _ = kernel_costs.roofline_share_pct(
        layers * flops, layers * nbytes, 1e-3 * ms, summary["peaks"])
    return share
