"""The ``sdar_30b_a3b_chat`` cell cut down to a size the tests run on the
CPU (see ``tiny.py``): every mechanism kept, every width shrunk."""
import copy

from benchmark import run
from benchmark.tests import tiny

CELL = "sdar_30b_a3b_chat.block_diffusion_8k"


def sdar(rows=2, seq=24):
    """(cell, configuration, traffic, limits): hidden 64, 4 query and 2
    key/value heads of 16 with head norms and rotary positions, two layers
    of 16 experts of 32 (4 held, top-3) behind the soft-max router, blocks
    of 4 positions, the mask token the last of 256 rows."""
    cell, cfg, traffic = run.resolve(tiny.manifest(), CELL)
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=256, mask_token_id=255, hidden_size=64,
               moe_intermediate_size=32, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               rope_theta=10000.0, num_experts=4, num_experts_published=16,
               num_experts_per_tok=3)
    traffic.update(batch_per_chip=rows, seq_len=seq)
    return cell, cfg, traffic, limits


def roomy(limits):
    """``tiny.roomy`` for this cell: three times the cell's limits, but
    fifteen times for the parameters' change after three steps. The cell's
    own change reads 1.5e-5 to 2.9e-5 by the median leaf on the chip
    (16,384 rows a layer behind every gradient, AdamW at 1e-5); with the
    48 rows a layer of this size an element's gradient is a few rows'
    rounding away from zero, AdamW's first steps are as large whatever the
    gradient's size, and the same number reads 1.3e-4 to 4.5e-4 over six
    seeds on the CPU (the worst leaf 0.0007 to 0.0063). A lost update
    reads a third and an unchanged state 1.0: both stay far outside."""
    return {k: (15 if k.startswith("delta_norm_gap") else 3) * v
            for k, v in limits.items()}
