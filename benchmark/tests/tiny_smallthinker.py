"""The ``smallthinker_21b_a3b`` cell cut down to a size the tests run on
the CPU (see ``tiny.py``): every mechanism kept, every width shrunk."""
import copy

from benchmark import run
from benchmark.tests import tiny

CELL = "smallthinker_21b_a3b.causal_pretrain_16k"


def smallthinker(rows=2, seq=24):
    """(cell, configuration, traffic, limits): hidden 64, 4 query and 2
    key/value heads of 16, four layers (one period: a global position-free
    layer and three rotated layers under a window of 8), 16 experts of 32
    (4 held, top-3) behind the soft-max router that reads the layer's
    input, 256 rows of vocabulary."""
    cell, cfg, traffic = run.resolve(tiny.manifest(), CELL)
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=256, hidden_size=64, moe_ffn_hidden_size=32,
               num_hidden_layers=4, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
               sliding_window_size=8, moe_num_primary_experts=4,
               moe_num_primary_experts_published=16,
               moe_num_active_primary_experts=3)
    traffic.update(batch_per_chip=rows, seq_len=seq)
    return cell, cfg, traffic, limits


def roomy(limits):
    """``tiny.roomy`` for this cell, as ``tiny_sdar.roomy``: three times
    the cell's limits for the losses and the gradient's worst leaf, but
    ten times for the gradient's median leaf and fifteen for the
    parameters' change after three steps. The cell's own readings have
    16,384 rows a layer behind every gradient (median leaf 0.00005 to
    0.00011 on the chip); with the 48 rows a layer of this size an
    element's gradient is a few rows' rounding away from zero, the median
    leaf reads 0.0011 on the CPU, and AdamW's first steps (at 1e-5) are as
    large whatever the gradient's size (the change reads 0.0002 by the
    median leaf and 0.0006 by the worst). A lost update reads a third and
    an unchanged state 1.0: both stay far outside."""
    def room(key):
        if key.startswith("delta_norm_gap"):
            return 15
        return 10 if key == "first_grad_norm_gap_median" else 3
    return {k: room(k) * v for k, v in limits.items()}
