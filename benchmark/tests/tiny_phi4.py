"""The ``phi4_mini_flash`` cell cut down to a size the tests run on the
CPU (see ``tiny.py``): every mechanism kept, every width shrunk."""
import copy

from benchmark import run
from benchmark.tests import tiny

CELL = "phi4_mini_flash.causal_pretrain"


def phi4(rows=2, seq=32):
    """(cell, configuration, traffic, limits): hidden 64, 4 query and 2
    key/value heads of 16 (two query pairs over one key/value pair), a
    window of 8, a feed-forward of 96, Mamba-1 with 128 channels, a state
    of 4 and a step-size rank of 4, the six layers of the cut (source
    layers 14-19 of the published 32: every kind and both hand-overs), 256
    rows of vocabulary; two sequences of 32 a step."""
    cell, cfg, traffic = run.resolve(tiny.manifest(), CELL)
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               num_attention_heads=4, num_key_value_heads=2,
               sliding_window=8, mamba_d_state=4, mamba_dt_rank=4)
    traffic.update(batch_per_chip=rows, seq_len=seq)
    return cell, cfg, traffic, limits


def roomy(limits):
    """``tiny.roomy`` for this cell, as ``tiny_lfm2.roomy``: three times
    the cell's limits for the losses, ten times for the gradient and
    fifteen for the parameters' change after three steps (64 rows a layer
    behind every gradient here where the cell has 8,192: an element's
    gradient is a few rows' rounding away from zero and AdamW's first steps
    are as large whatever the gradient's size; read on the CPU at this
    size over three seeds, as multiples of the cell's limits: the
    gradient's median leaf 1.1-3.5, its worst 0.4-0.6, the change 6.0-7.2
    and 2.1-2.6, the losses under 0.3). A lost update reads a third and an
    unchanged state 1.0: both stay far outside, as do the planted
    faults."""
    def room(key):
        if key.startswith("delta_norm_gap"):
            return 15
        return 10 if key.startswith("first_grad_norm_gap") else 3
    return {k: room(k) * v for k, v in limits.items()}
