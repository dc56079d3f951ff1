"""Parameters, model flops and least HBM bytes of a ``smallthinker``
configuration under causal pre-training, from its sizes alone: what the
cell's MFU note, its ``win_flash_roofline`` and its
``st_global_flash_roofline`` divide by. Kept with the benchmark (see
``kernel_costs.py``) so that no later PR moves the yardstick.

Model flops follow the MFU convention: what the forward and the backward
pass need (2 flops a multiply-add; backward twice the forward),
recomputation and the optimizer not counted. Attention reads the pairs its
layer allows and no others: a global layer the causal half of the square,
a window layer the band of ``sliding_window_size`` keys under the
diagonal.

``cfg`` is the configuration as run (benchmark/configs/<name>.json):
``moe_num_primary_experts`` counts the experts held here,
``moe_num_primary_experts_published`` the router's width,
``num_hidden_layers`` the layers held; the two per-layer lists may keep
their published length, the first ``num_hidden_layers`` entries are read.
"""
from benchmark import kernel_costs


def attention_params(cfg):
    """{part: parameters} of one attention layer (no bias, no head norm)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q_proj": d * heads * hd, "k_proj": d * kv * hd,
            "v_proj": d * kv * hd, "o_proj": heads * hd * d}


def expert_params(cfg):
    """One routed expert: gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_ffn_hidden_size"]


def layer_params(cfg):
    """{part: parameters} of one block as held here."""
    d = cfg["hidden_size"]
    return {"attention": sum(attention_params(cfg).values()),
            "vectors": 2 * d,
            "router": d * cfg["moe_num_primary_experts_published"],
            "routed": cfg["moe_num_primary_experts"] * expert_params(cfg)}


def total_params(cfg):
    """Everything held here: the blocks, the embedding slice, the untied
    head slice and the final norm."""
    return cfg["num_hidden_layers"] * sum(layer_params(cfg).values()) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def windowed_layers(cfg):
    """[0 | 1] a held layer: whether its attention has the window."""
    return list(cfg["sliding_window_layout"][:cfg["num_hidden_layers"]])


def allowed_pairs(seq_len, window=None):
    """(row, key) pairs of one sequence a head may read: ``j <= i`` and,
    under a window, ``i - j < window``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def layer_pairs(cfg, seq_len):
    """Allowed pairs a head, layer by held layer."""
    return [allowed_pairs(seq_len,
                          cfg["sliding_window_size"] if windowed else None)
            for windowed in windowed_layers(cfg)]


def slots_here_per_row(cfg):
    """Expected (row, choice) slots a row routes to the experts held here,
    under a router that spreads evenly."""
    return cfg["moe_num_active_primary_experts"] \
        * cfg["moe_num_primary_experts"] \
        / cfg["moe_num_primary_experts_published"]


def moe_forward_flops_per_row(cfg, slots_here=None):
    if slots_here is None:
        slots_here = slots_here_per_row(cfg)
    return 2 * layer_params(cfg)["router"] \
        + 2 * slots_here * expert_params(cfg)


def forward_flops_per_token(cfg, seq_len):
    """{part: forward flops a token}: ``scores`` the two products of
    attention over the pairs each layer allows."""
    layers, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    pairs = sum(layer_pairs(cfg, seq_len)) / seq_len
    return {
        "projections": layers * 2 * sum(attention_params(cfg).values()),
        "scores": cfg["num_attention_heads"] * pairs * 2 * 2 * hd,
        "moe": layers * moe_forward_flops_per_row(cfg),
        "head": 2.0 * cfg["hidden_size"] * cfg["vocab_size"]}


def train_flops_per_token(cfg, seq_len):
    """Model flops a token of a training step: forward plus backward."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def attention_kernel_costs(cfg, seq_len, windowed, batch=1, itemsize=2):
    """(flops, least HBM bytes) of the attention kernels of ONE block,
    forward + backward, over the pairs its kind allows at head size
    ``d``: forward QK^T and PV; backward (Dao et al. arXiv:2205.14135
    algorithm 4) QK^T again, dP = dO V^T, dV = P^T dO, dQ = dS K, dK =
    dS^T Q: seven products of ``2 d`` flops a pair and head. Bytes, each
    operand once: Q, O, dO and dQ by query head; K, V, dK and dV by
    key/value head, whatever an implementation repeats. The forward reads
    Q, K, V and writes O; the backward reads Q, K, V, O, dO and writes dQ,
    dK, dV."""
    hd = cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    pairs = allowed_pairs(seq_len,
                          cfg["sliding_window_size"] if windowed else None)
    flops = 7 * 2.0 * batch * heads * pairs * hd
    rows = batch * seq_len * itemsize * hd
    q, k = rows * heads, rows * kv
    return flops, (2 * q + 2 * k) + (4 * q + 4 * k)


def flash_roofline_pct(cfg, traffic, windowed, seconds, peaks):
    """Share of their roofline of the flash kernels of the step's layers of
    one kind (``windowed`` 1 or 0) that took ``seconds`` of device time a
    step; None where the configuration holds no layer of the kind."""
    layers = windowed_layers(cfg).count(windowed)
    if not layers:
        return None
    flops, nbytes = attention_kernel_costs(
        cfg, traffic["seq_len"], windowed, traffic["batch_per_chip"])
    return kernel_costs.roofline_share_pct(
        layers * flops, layers * nbytes, seconds, peaks)[0]
