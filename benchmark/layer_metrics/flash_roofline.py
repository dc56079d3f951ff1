"""The flash-attention kernels' share of their roofline: the least time
the chip could take for the attention the step needs (kernel_costs: the
larger of flops / peak flops and bytes / peak bytes per second, forward
plus backward, every layer) over the device time of the flash kernels in
the trace. The kernels carry no name of their own; a flash kernel is a
Mosaic custom call with a 3-D [batch x heads, seq, head_dim] operand
(layer norm's are 2-D). At
BERT-base's shapes the bound is flops. Nothing to read where the step holds
no such kernel (sequences under ``flash_min_seq``)."""
import re

from benchmark import kernel_costs

LAYER = "ops"
UNIT = "%"
MOVES = "step_ms"

_THREE_D = re.compile(r"\[\d+,\d+,\d+\]")


def read(summary, counters, context):
    seconds = sum(sec for sig, sec in summary.get("kernels", ())
                  if _THREE_D.search(sig))
    cfg, traffic = context["config"], context["traffic"]
    if not seconds or not summary.get("steps") or "seq_len" not in traffic:
        return None
    heads, seq = cfg["num_attention_heads"], traffic["seq_len"]
    shape = (traffic["batch_per_chip"], heads, seq, seq,
             cfg["hidden_size"] // heads)
    calls = cfg["num_hidden_layers"] * summary["steps"]
    flops = calls * sum(kernel_costs.flash_attention_flops(*shape, b)
                        for b in (False, True))
    nbytes = calls * sum(kernel_costs.flash_attention_bytes(*shape, 2, b)
                         for b in (False, True))
    share, _ = kernel_costs.roofline_share_pct(flops, nbytes, seconds,
                                               summary["peaks"])
    return share
