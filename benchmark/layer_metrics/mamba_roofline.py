"""The Mamba-2 mixers' share of their roofline: the least time the chip
could take for the model flops and least HBM bytes of all Mamba-2 blocks
of a step, forward + backward (``benchmark/nemotron_h_costs.py``: the
two projections, the convolution, the chunked scan; weights read twice
and their gradient written, the residual stream in and out), over the
device time of the regions ``Mamba2Mixer_<k>`` (``mamba_ms_per_step``).
The recomputed forward is in the time and not in the flops, so the share
cannot pass 100. At these widths the bound is flops."""
from benchmark import nemotron_h_costs, region_time

LAYER = "model"
UNIT = "%"
MOVES = "step_ms"


def read(summary, counters, context):
    ms = region_time.class_ms(summary, context, "Mamba2Mixer")
    cfg, traffic = context["config"], context["traffic"]
    if ms is None or "seq_len" not in traffic:
        return None
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    share, _ = nemotron_h_costs.kind_roofline_pct(
        cfg, "M", traffic["seq_len"], tokens, 1e-3 * ms, summary["peaks"])
    return share
