"""The selection kernel's share of its roofline: the least time the chip
could take for the index scores of every causal pair
(``keye_vl_costs.select_kernel_costs``: sixteen 64-deep dots a pair; reads
qI, kI and w, writes the int8 selection), once for every call of the
kernel named ``dsa_select`` that the step holds, over those calls' device
time. A recomputed block makes its selection again in the backward pass:
that call is in the time AND in the flops (the step's instructions that
hold the kernel are counted, ``monitor.profile.instruction_ledger``), so a
step that kept the selection would read what this one reads. The
threshold's counting passes are no matrix work, so the share cannot pass
100. Nothing to read while the op runs its XLA route
(``dsa.select.xla_traced``)."""
from benchmark import keye_vl_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"
KERNEL = "dsa_select"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "keye_vl" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, KERNEL)
    if ms is None:
        return None
    calls = sum(1 for row in (program_trace.ledger() or {}).values()
                if row.get("kernel") == KERNEL)
    if not calls:
        return None
    return keye_vl_costs.roofline_pct(
        keye_vl_costs.select_kernel_costs, cfg, traffic, 1e-3 * ms,
        summary["peaks"], calls=calls)
