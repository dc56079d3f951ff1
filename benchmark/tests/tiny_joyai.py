"""The ``joyai_llm_flash`` cell cut down to a size the tests run on the CPU
(see ``tiny.py``): every mechanism kept, every width shrunk."""
import copy

from benchmark import run
from benchmark.tests import tiny

CELL = "joyai_llm_flash.causal_pretrain"


def joyai(rows=2, seq=24):
    """(cell, configuration, traffic, limits): hidden 64, 4 heads of 16 + 8
    / 16 through latents of 48 and 32, a dense layer of 96 and two expert
    layers (16 experts of 32, 4 held, top-3), the prediction module."""
    cell, cfg, traffic = run.resolve(tiny.manifest(), CELL)
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=256, hidden_size=64, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=3,
               num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               rope_theta=10000.0, n_routed_experts=4,
               n_routed_experts_published=16, num_experts_per_tok=3)
    traffic.update(batch_per_chip=rows, seq_len=seq)
    return cell, cfg, traffic, limits
