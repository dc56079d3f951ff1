"""The ``lfm2_8b_a1b`` cell cut down to a size the tests run on the CPU
(see ``tiny.py``): every mechanism kept, every width shrunk."""
import copy

from benchmark import run
from benchmark.tests import tiny

CELL = "lfm2_8b_a1b.causal_pretrain_2x8k"


def lfm2(rows=8, seq=8):
    """(cell, configuration, traffic, limits): hidden 64, 4 query and 2
    key/value heads of 16, the five layers of the cut (a conv layer over a
    dense feed-forward of 96, an attention layer and three conv layers over
    experts: 16 of 32 wide, 4 held, top-3) behind the sigmoid router, 256
    rows of vocabulary, the default ladder of capacities; eight sequences
    of 8 a step, so that a fifth of the rows stand within ``taps - 1`` of
    a sequence's start."""
    cell, cfg, traffic = run.resolve(tiny.manifest(), CELL)
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_attention_heads=4,
               num_key_value_heads=2, rope_theta=10000.0, num_experts=4,
               num_experts_published=16, num_experts_per_tok=3)
    traffic.update(batch_per_chip=rows, seq_len=seq)
    return cell, cfg, traffic, limits


def roomy(limits):
    """``tiny.roomy`` for this cell, as ``tiny_smallthinker.roomy``: three
    times the cell's limits for the losses and the gradient's worst leaf,
    ten times for the gradient's median leaf and fifteen for the
    parameters' change after three steps (64 rows a layer behind every
    gradient here where the cell has 16,384: an element's gradient is a few
    rows' rounding away from zero and AdamW's first steps are as large
    whatever the gradient's size; read on the CPU at this size, as
    multiples of the cell's limits: the gradient's median leaf 1.7-1.8, its
    worst 0.6-0.7, the change 3.1-4.2 and 3.4-5.4, the losses under 0.25).
    A lost update reads a third and an unchanged state 1.0: both stay far
    outside, as do the two planted faults (the sequences run together read
    23 x the limit by the gradient's worst leaf, the gates left out
    10,000 x)."""
    def room(key):
        if key.startswith("delta_norm_gap"):
            return 15
        return 10 if key == "first_grad_norm_gap_median" else 3
    return {k: room(k) * v for k, v in limits.items()}
