"""The new cell cut down to a size the tests run on the CPU (see
``tiny.py``): every mechanism kept, every width shrunk."""
import copy

from benchmark import run
from benchmark.tests import tiny

CELL = "nemotron3_nano_30b_a3b.causal_pretrain"


def nemotron(rows=2, seq=24):
    """(cell, configuration, traffic, limits): hidden 64, 4 Mamba heads x
    16 with state 16 in 2 groups and chunks of 8, attention 4 / 2 x 16,
    16 experts of which 4 are held, top-3, pattern ``ME*E``."""
    cell, cfg, traffic = run.resolve(tiny.manifest(), CELL)
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=256, hidden_size=64, num_hidden_layers=4,
               hybrid_override_pattern="ME*E", mamba_num_heads=4,
               mamba_head_dim=16, ssm_state_size=16, n_groups=2,
               chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, n_routed_experts=4,
               n_routed_experts_published=16, num_experts_per_tok=3,
               moe_intermediate_size=32,
               moe_shared_expert_intermediate_size=64)
    traffic.update(batch_per_chip=rows, seq_len=seq)
    return cell, cfg, traffic, limits
