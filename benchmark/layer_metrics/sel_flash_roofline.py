"""The flash kernels' share of the MXU peak under a selection: the least
time the chip could take for the attention that the step's layers need
(``keye_vl_costs.selected_flash_costs``: the flops of the SELECTED pairs
alone at every query head, two products forward and five backward, each
operand's bytes once) over the device time of the kernels named
``flash_sel_fwd`` / ``flash_sel_bwd``. The kernels walk every causal tile
(a token-level selection empties none), so the share reads about the
selected share of what the same kernels read dense; a kernel that walked
fewer tiles would read higher. The forward runs once a layer (its results
are kept by name), so time and flops cover the same calls."""
from benchmark import keye_vl_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "keye_vl" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, "flash_sel_")
    if ms is None:
        return None
    return keye_vl_costs.roofline_pct(keye_vl_costs.selected_flash_costs, cfg, traffic,
                                      1e-3 * ms, summary["peaks"])
