"""The selective-scan kernels' share of the chip's roofline: the least time
for one forward and one backward call a Mamba layer
(``phi4_flash_costs.scan_kernel_costs``: the flops of the recurrence, 9 a
(token, channel, state) forward and three times that backward, AT THE
MXU'S RATE, and the bytes of ``x``, the step sizes, ``z``, ``B``, ``C`` in,
``y`` and the gated result out, then their gradients back) over the device
time of the kernels named ``selective_scan_fwd`` / ``selective_scan_bwd``.
The kernels' own bound is neither: they run on the VECTOR AND
TRANSCENDENTAL UNITS, for which ``peaks.json`` has no peak, so the share
reads low by construction (HBM bytes are the larger of the two least
times); ``sscan_ms_per_step`` is the number to watch. A recomputed forward
kernel is in the time and not in the work, so the share cannot pass 100."""
from benchmark import phi4_flash_costs, program_trace

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    cfg, traffic = context["config"], context["traffic"]
    if cfg.get("family") != "phi4_flash" or "seq_len" not in traffic:
        return None
    ms = program_trace.kernel_ms(summary, context, "selective_scan_")
    if ms is None:
        return None
    return phi4_flash_costs.sscan_roofline_pct(cfg, traffic, 1e-3 * ms,
                                               summary["peaks"])
