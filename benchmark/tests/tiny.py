"""The tiny sizes the tests run on the CPU. They live here, not as an
option of run.py."""
import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 16e9}
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def bert(hidden=64, layers=2, heads=2, rows=4, seq=16):
    """(cell, configuration, traffic, limits) of the first BERT cell, cut
    down."""
    from benchmark import run
    cell, cfg, traffic = run.resolve(manifest(), "bert_base.pretrain_seq128")
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=512, hidden_size=hidden, num_hidden_layers=layers,
               num_attention_heads=heads, intermediate_size=2 * hidden,
               max_position_embeddings=64)
    traffic.update(batch_per_chip=rows, seq_len=seq)
    return cell, cfg, traffic, limits


def roomy(limits):
    """A cell's limits are set from its own readings on the chip. The
    program at a tiny size on the CPU reads wider, so the tests that drive
    it hold it to three times the cell's; the control is held to the
    cell's own."""
    return {k: 3 * v for k, v in limits.items()}
