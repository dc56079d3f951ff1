"""The ``keye_vl2_30b_a3b`` cell cut down to a size the tests run on the
CPU (see ``tiny.py``): every mechanism kept, every width shrunk."""
import copy

from benchmark import run
from benchmark.tests import tiny

CELL = "keye_vl2_30b_a3b.sparse_causal_16k"


def keye(rows=2, seq=48, spans=2, grid=(3, 4), compute_dtype=None):
    """(cell, configuration, traffic, limits): hidden 64, 4 query and 2
    key/value heads of 16 with head norms and three-axis rotary positions
    (chunks of 2, 3 and 3 pairs), an indexer of 4 heads of 8 that keeps 8
    keys a row, two layers of 16 experts of 32 (4 held, top-3) behind the
    soft-max router, 256 rows of vocabulary; two image spans of 3 x 4
    positions a sequence of 48. ``compute_dtype`` in the place of the
    configuration's bfloat16: a fault smaller than 96 rows' bfloat16
    rounding shows in float32."""
    cell, cfg, traffic = run.resolve(tiny.manifest(), CELL)
    limits = run.cell_limits(cell)
    cfg, traffic = copy.deepcopy(cfg), copy.deepcopy(traffic)
    cfg.update(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
               num_experts=4, num_experts_published=16,
               num_experts_per_tok=3)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], mrope_section=[2, 3, 3])
    cfg["sa_config"] = dict(cfg["sa_config"], indexer_num_heads=4,
                            indexer_head_dim=8, topk=8)
    if compute_dtype is not None:
        cfg["assumed"]["compute_dtype"] = compute_dtype
    traffic.update(batch_per_chip=rows, seq_len=seq, image_spans=spans,
                   image_grid=list(grid))
    return cell, cfg, traffic, limits


def roomy(limits):
    """``tiny.roomy`` for this cell. The cell's limits are set from 16,384
    rows a layer on the chip, where the float32 islands hold both losses to
    7e-6 of the reference's and every leaf's gradient averages thousands
    of rows' roundings; the 96 rows a layer of this size in bfloat16 on a
    CPU read, as multiples of the cell's limits over three seeds: the
    losses up to 6.0, the gradient's median leaf 0.3 and its worst 2.5, the
    change 4.2 and 6.2 (of the first loss's limit as it was then, 2.0e-5:
    the review's last call moved it to 4.8e-5). So: 6e-4 and 1.1e-3 of a
    loss of 5.7 for the losses (12.5 and 30 times the cell's limits), ten
    times the cell's for the gradient's median leaf,
    six for its worst, fifteen for the parameters' change after three
    steps. A lost update reads a third and an unchanged state 1.0: both
    stay far outside, as do two planted faults (the selection left out
    reads 16 x these by the first loss, L_I left out 53 x, and 1.0 by the
    gradient's worst leaf). The third, the position rows collapsed, moves
    the worst leaf's gradient by 0.9 % at this size (the ids of a 3 x 4
    span differ by 3 at the most), under this size's bfloat16 rounding:
    its test takes one span of 8 x 12 in 128 positions, runs the step in
    float32 and holds the gradient to the cell's own limits
    (``float32_room``)."""
    def room(key):
        if key.startswith("loss_gap"):
            return 12.5 if key == "loss_gap_first" else 30
        if key.startswith("delta_norm_gap"):
            return 15
        return 10 if key == "first_grad_norm_gap_median" else 6
    return {k: room(k) * v for k, v in limits.items()}


def float32_room(limits):
    """The cell's own limits for the gradient, 1e-4 and 1.9e-4 for the
    losses (two and five times the cell's) and fifteen times the cell's
    for the change: what the sound step reads in float32 at
    this size (the gradient's worst leaf 0.07 of the cell's limit; the
    third loss 1.8 x and the change 2.5-3.1 x, AdamW's first steps at 96
    rows)."""
    return {k: (1 if k.startswith("first_grad") else
                2 if k == "loss_gap_first" else
                5 if k.startswith("loss") else 15) * v
            for k, v in limits.items()}
