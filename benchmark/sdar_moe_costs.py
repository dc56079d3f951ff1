"""Parameters, model flops and least HBM bytes of an ``sdar_moe``
configuration under block-diffusion training, from its sizes alone: what
the cell's MFU note and its ``bd_flash_roofline`` divide by. Kept with the
benchmark (see ``kernel_costs.py``) so that no later PR moves the
yardstick.

Model flops follow the MFU convention: what the forward and the backward
pass need (2 flops a multiply-add; backward twice the forward),
recomputation and the optimizer not counted. A step passes TWO rows for
every data token (its noisy and its clean copy) through every block, and
attention reads the pairs the block-diffusion structure allows and no
others; only the noisy copy's rows go through the head. The unit is a
DATA token.

``cfg`` is the configuration as run (benchmark/configs/<name>.json):
``num_experts`` counts the experts held here, ``num_experts_published``
the router's width, ``num_hidden_layers`` the layers held.
"""


def attention_params(cfg):
    """{part: parameters} of one attention layer; its two head norms
    under ``vectors``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q_proj": d * heads * hd, "k_proj": d * kv * hd,
            "v_proj": d * kv * hd, "o_proj": heads * hd * d,
            "vectors": 2 * hd}


def expert_params(cfg):
    """One routed expert: gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_params(cfg):
    """{part: parameters} of one block as held here."""
    d = cfg["hidden_size"]
    return {"attention": sum(attention_params(cfg).values()),
            "vectors": 2 * d,
            "router": d * cfg["num_experts_published"],
            "routed": cfg["num_experts"] * expert_params(cfg)}


def total_params(cfg):
    """Everything held here: the blocks, the embedding slice, the untied
    head slice and the final norm."""
    return cfg["num_hidden_layers"] * sum(layer_params(cfg).values()) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def allowed_pairs(seq_len, block):
    """(row, key) pairs the structure allows over the ``2 seq_len`` rows
    of one sequence: clean rows over the clean blocks up to their own
    (``L^2 / 2 + L B / 2``), noisy rows over the clean blocks before their
    own (``L^2 / 2 - L B / 2``) and over their own noisy block (``L B``)."""
    return seq_len * seq_len + seq_len * block


def slots_here_per_row(cfg):
    """Expected (row, choice) slots a row routes to the experts held here,
    under a router that spreads evenly."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]


def moe_forward_flops_per_row(cfg, slots_here=None):
    if slots_here is None:
        slots_here = slots_here_per_row(cfg)
    return 2 * layer_params(cfg)["router"] \
        + 2 * slots_here * expert_params(cfg)


def forward_flops_per_token(cfg, seq_len):
    """{part: forward flops a DATA token}: ``projections`` and ``moe`` on
    both copies' rows, ``scores`` the two products of attention over the
    allowed pairs, ``head`` on the noisy copy's row."""
    a = attention_params(cfg)
    hd, layers = cfg["head_dim"], cfg["num_hidden_layers"]
    pairs = allowed_pairs(seq_len, cfg["block_length"]) / seq_len
    return {
        "projections": layers * 2 * 2 * (sum(a.values()) - a["vectors"]),
        "scores": layers * 2 * cfg["num_attention_heads"] * pairs * 2 * hd,
        "moe": layers * 2 * moe_forward_flops_per_row(cfg),
        "head": 2.0 * cfg["hidden_size"] * cfg["vocab_size"]}


def train_flops_per_token(cfg, seq_len):
    """Model flops a data token of a training step: forward plus
    backward."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def attention_kernel_costs(cfg, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of the attention kernels of one block,
    forward + backward, over the allowed pairs at head size ``d``: forward
    QK^T and PV; backward (Dao et al. arXiv:2205.14135 algorithm 4) QK^T
    again, dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q: seven
    products of ``2 d`` flops a pair and head. Bytes, each operand once
    over the ``2 seq_len`` rows: Q, O, dO and dQ by query head; K, V, dK
    and dV by key/value head, whatever an implementation repeats. The
    forward reads Q, K, V and writes O; the backward reads Q, K, V, O, dO
    and writes dQ, dK, dV."""
    hd = cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = allowed_pairs(seq_len, cfg["block_length"])
    flops = 7 * 2.0 * batch * heads * pairs * hd
    rows = batch * 2 * seq_len * itemsize * hd
    q, k = rows * heads, rows * kv
    return flops, (2 * q + 2 * k) + (4 * q + 4 * k)
