"""The flash-attention kernels' share of their roofline at a latent-
attention causal shape: the least time the chip could take for the
attention that the step's blocks need (``joyai_llm_flash_costs.
attention_kernel_costs``: causal flops with q and k at ``qk_nope_head_dim +
qk_rope_head_dim`` and v at ``v_head_dim``, each operand's bytes once,
forward + backward; every main block and the multi-token-prediction
module's) over the device time of the kernels named ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` (``flash_ms_per_step``). A
recomputed forward kernel is in the time and not in the flops, so the
share cannot pass 100."""
from benchmark import joyai_llm_flash_costs, kernel_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    ms = program_trace.kernel_ms(summary, context, "flash_")
    cfg, traffic = context["config"], context["traffic"]
    if ms is None or "kv_lora_rank" not in cfg or "seq_len" not in traffic:
        return None
    layers = len(joyai_llm_flash_costs.layer_kinds(cfg))
    flops, nbytes = joyai_llm_flash_costs.attention_kernel_costs(
        cfg, traffic["seq_len"], traffic["batch_per_chip"])
    share, _ = kernel_costs.roofline_share_pct(
        layers * flops, layers * nbytes, 1e-3 * ms, summary["peaks"])
    return share
