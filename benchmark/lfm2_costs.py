"""Parameters, model flops and least HBM bytes of an ``lfm2_moe``
configuration under causal pre-training, from its sizes alone: what the
cell's MFU note, its ``lfm2_shortconv_roofline`` and its
``lfm2_flash_roofline`` divide by. Kept with the benchmark (see
``kernel_costs.py``) so that no later PR moves the yardstick.

Model flops follow the MFU convention: what the forward and the backward
pass need (2 flops a multiply-add; backward twice the forward),
recomputation and the optimizer not counted. The gates and taps of the
short convolution are counted (8 flops a row and channel with 3 taps: a
three-hundredth of its two projections).

``cfg`` is the configuration as run (benchmark/configs/<name>.json):
``num_experts`` counts the experts held here, ``num_experts_published`` the
router's width, ``num_hidden_layers`` the layers held, of which the first
``num_dense_layers`` are dense; ``layer_types`` may keep its published
length, the entries from ``first_layer`` on are read.
"""
from benchmark import kernel_costs
from benchmark.reference.lfm2_moe import head_dim, layer_kinds  # noqa: F401


def conv_params(cfg):
    """{part: parameters} of one gated short-convolution operator."""
    d = cfg["hidden_size"]
    return {"in_proj": d * 3 * d, "taps": d * cfg["conv_L_cache"],
            "out_proj": d * d}


def attention_params(cfg):
    """{part: parameters} of one attention operator (no bias; one norm
    scale a side)."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q_proj": d * heads * hd, "k_proj": d * kv * hd,
            "v_proj": d * kv * hd, "o_proj": heads * hd * d,
            "head_norms": 2 * hd}


def dense_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One routed expert: gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts_published"]


def layer_params(cfg, kind, dense):
    """Parameters of one block as held here: operator, feed-forward, two
    norms."""
    op = conv_params(cfg) if kind == "conv" else attention_params(cfg)
    ff = dense_params(cfg) if dense else \
        router_params(cfg) + cfg["num_experts"] * expert_params(cfg)
    return sum(op.values()) + ff + 2 * cfg["hidden_size"]


def total_params(cfg):
    """Everything held here: the blocks, the embedding slice (which is the
    head) and the final norm."""
    return sum(layer_params(cfg, *k) for k in layer_kinds(cfg)) \
        + cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def causal_pairs(seq_len):
    """(row, key) pairs of one sequence a head may read: ``j <= i``."""
    return seq_len * (seq_len + 1) // 2


def slots_here_per_row(cfg):
    """Expected (row, choice) slots a row routes to the experts held here,
    under a router that spreads evenly."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / cfg["num_experts_published"]


def forward_flops_per_token(cfg, seq_len):
    """{part: forward flops a token}: ``conv`` the short-convolution
    operators (two projections, two gates, the taps), ``attention`` the
    attention operators' projections, ``scores`` the two products of
    attention over the causal pairs."""
    kinds = layer_kinds(cfg)
    convs = sum(kind == "conv" for kind, _ in kinds)
    attns = len(kinds) - convs
    denses = sum(dense for _, dense in kinds)
    d = cfg["hidden_size"]
    conv = conv_params(cfg)
    attention = attention_params(cfg)
    return {
        "conv": convs * (2 * (conv["in_proj"] + conv["out_proj"])
                         + 2 * conv["taps"] + 2 * d),
        "attention": attns * 2 * (sum(attention.values())
                                  - attention["head_norms"]),
        "scores": attns * cfg["num_attention_heads"]
        * causal_pairs(seq_len) / seq_len * 2 * 2 * head_dim(cfg),
        "dense": denses * 2 * dense_params(cfg),
        "moe": (len(kinds) - denses) * (
            2 * router_params(cfg)
            + 2 * slots_here_per_row(cfg) * expert_params(cfg)),
        "head": 2.0 * d * cfg["vocab_size"]}


def train_flops_per_token(cfg, seq_len):
    """Model flops a token of a training step: forward plus backward."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def shortconv_kernel_bytes(cfg, traffic, itemsize=2):
    """(forward, backward) least HBM bytes of ONE call of the gated
    short-convolution kernels: with ``n = B S C itemsize`` the forward
    reads b, c, u and writes y (4 n); the backward reads b, c, u, dy and
    writes db, dc, du (7 n). The taps and their gradient (``C K`` floats)
    are left out."""
    n = traffic["batch_per_chip"] * traffic["seq_len"] \
        * cfg["hidden_size"] * itemsize
    return 4 * n, 7 * n


def shortconv_roofline_pct(cfg, traffic, seconds, peaks):
    """Share of the HBM peak of the step's gated short-convolution kernels
    that took ``seconds`` of device time a step: one forward and one
    backward call a conv layer. A recomputed forward kernel is in the time
    and not in the bytes. None where the configuration holds no conv
    layer."""
    convs = sum(kind == "conv" for kind, _ in layer_kinds(cfg))
    if not convs:
        return None
    fwd, bwd = shortconv_kernel_bytes(cfg, traffic)
    return 100.0 * convs * (fwd + bwd) / peaks["hbm_bytes_per_s"] / seconds


def attention_kernel_costs(cfg, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of the causal attention kernels of ONE
    block, forward + backward, at head size ``hidden / heads``: forward
    QK^T and PV; backward (Dao et al. arXiv:2205.14135 algorithm 4) QK^T
    again, dP = dO V^T, dV = P^T dO, dQ = dS K, dK = dS^T Q: seven
    products of ``2 d`` flops a pair and head. Bytes, each operand once:
    Q, O, dO and dQ by query head; K, V, dK and dV by key/value head,
    whatever an implementation repeats."""
    hd = head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = 7 * 2.0 * batch * heads * causal_pairs(seq_len) * hd
    rows = batch * seq_len * itemsize * hd
    q, k = rows * heads, rows * kv
    return flops, (2 * q + 2 * k) + (4 * q + 4 * k)


def flash_roofline_pct(cfg, traffic, seconds, peaks):
    """Share of their roofline of the flash kernels of the step's attention
    layers that took ``seconds`` of device time a step; None where the
    configuration holds no attention layer."""
    layers = sum(kind == "full_attention" for kind, _ in layer_kinds(cfg))
    if not layers:
        return None
    flops, nbytes = attention_kernel_costs(
        cfg, traffic["seq_len"], traffic["batch_per_chip"])
    return kernel_costs.roofline_share_pct(
        layers * flops, layers * nbytes, seconds, peaks)[0]
