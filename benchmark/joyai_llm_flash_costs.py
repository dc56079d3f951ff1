"""Parameters, model flops and least HBM bytes of a ``joyai_llm_flash``
configuration, from its sizes alone: what the cell's MFU note and its
``mla_flash_roofline`` / ``moe_gated_roofline`` divide by. Kept with the
benchmark (see ``kernel_costs.py``) so that no later PR moves the
yardstick.

Model flops follow the MFU convention: what the forward and the backward
pass need (2 flops a multiply-add; backward twice the forward),
recomputation and the optimizer not counted; causal attention reads half
of the S x S products.

``cfg`` is the configuration as run (benchmark/configs/<name>.json):
``n_routed_experts`` counts the experts held here,
``n_routed_experts_published`` the router's width, ``num_hidden_layers``
the main layers held (the multi-token-prediction module comes on top).
"""

KINDS = ("D", "E", "P")     # dense block, expert block, prediction module


def qk_head_dim(cfg):
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def layer_kinds(cfg):
    """``D`` or ``E`` a main layer (the reference's rule), then ``P`` a
    prediction module."""
    from benchmark.reference import joyai_llm_flash as reference
    return reference.layer_kinds(cfg) + "P" * cfg["num_nextn_predict_layers"]


def mla_params(cfg):
    """{part: parameters} of one multi-head latent attention, its two
    latent norms under ``vectors``."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return {"q_a_proj": d * rq, "q_b_proj": rq * h * qk_head_dim(cfg),
            "kv_a_proj_with_mqa": d * (rkv + cfg["qk_rope_head_dim"]),
            "kv_b_proj": rkv * h * (cfg["qk_nope_head_dim"]
                                    + cfg["v_head_dim"]),
            "o_proj": h * cfg["v_head_dim"] * d, "vectors": rq + rkv}


def expert_params(cfg):
    """One routed expert: gate, up and down matrices."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def ffn_params(cfg, kind):
    """{part: parameters} of the feed-forward half of a block as held
    here."""
    d = cfg["hidden_size"]
    if kind == "D":
        return {"dense": 3 * d * cfg["intermediate_size"]}
    if kind in ("E", "P"):
        return {"router": d * cfg["n_routed_experts_published"],
                "shared": cfg["n_shared_experts"] * expert_params(cfg),
                "routed": cfg["n_routed_experts"] * expert_params(cfg)}
    raise ValueError(f"layer kind {kind!r} is not one of {KINDS}")


def layer_params(cfg, kind):
    """{part: parameters} of one block of ``kind`` as held here: the
    attention, the feed-forward half, the block's two norms and, for the
    prediction module, ``eh_proj`` and its three norms."""
    d = cfg["hidden_size"]
    out = {"mla": sum(mla_params(cfg).values()), "vectors": 2 * d}
    out.update(ffn_params(cfg, kind))
    if kind == "P":
        out["eh_proj"] = 2 * d * d
        out["vectors"] += 3 * d
    return out


def total_params(cfg):
    """Everything held here: the blocks, the prediction module, the
    embedding slice, the untied head slice and the final norm."""
    blocks = sum(sum(layer_params(cfg, k).values())
                 for k in layer_kinds(cfg))
    return blocks + 2 * cfg["vocab_size"] * cfg["hidden_size"] \
        + cfg["hidden_size"]


def slots_here_per_token(cfg):
    """Expected (token, choice) slots a token routes to the experts held
    here, under a router that spreads evenly."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["n_routed_experts_published"]


def ffn_forward_flops_per_token(cfg, kind, slots_here=None):
    p = ffn_params(cfg, kind)
    if kind == "D":
        return 2 * p["dense"]
    if slots_here is None:
        slots_here = slots_here_per_token(cfg)
    return 2 * (p["router"] + p["shared"]) \
        + 2 * slots_here * expert_params(cfg)


def mla_forward_flops_per_token(cfg, seq_len):
    """Projections, plus the causal half of QK^T (at the q/k head size)
    and of PV (at the v head size)."""
    m = mla_params(cfg)
    core = 2 * cfg["num_attention_heads"] * (seq_len / 2) \
        * (qk_head_dim(cfg) + cfg["v_head_dim"])
    return 2 * (sum(m.values()) - m["vectors"]) + core


def forward_flops_per_token(cfg, seq_len):
    """{part: forward flops a token}: ``mla`` and ``dense`` / ``moe`` over
    the main blocks, ``mtp`` the whole prediction module with its head
    pass, ``head`` the main head."""
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    out = {"mla": 0.0, "dense": 0.0, "moe": 0.0, "mtp": 0.0, "head": head}
    for kind in layer_kinds(cfg):
        mla = mla_forward_flops_per_token(cfg, seq_len)
        ffn = ffn_forward_flops_per_token(cfg, kind)
        if kind == "P":
            out["mtp"] += mla + ffn + head \
                + 2 * layer_params(cfg, "P")["eh_proj"]
        else:
            out["mla"] += mla
            out["dense" if kind == "D" else "moe"] += ffn
    return out


def train_flops_per_token(cfg, seq_len):
    """Model flops a token of a training step: forward plus backward."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def attention_kernel_costs(cfg, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of the attention kernels of one block,
    forward + backward, causal, at q/k head size ``d`` and v head size
    ``dv``: half of the S x S products of exact attention — forward QK^T
    (d) and PV (dv); backward (Dao et al. arXiv:2205.14135 algorithm 4)
    QK^T again (d), dP = dO V^T (dv), dV = P^T dO (dv), dQ = dS K (d), dK
    = dS^T Q (d). Bytes, each operand once: Q and dQ by head at d; O and dO
    by head at dv; V and dV by head at dv; K and dK by head at the
    position-free width plus the ONE rotary key head, whatever an
    implementation repeats. The forward reads Q, K, V and writes O; the
    backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    h, d, dv = cfg["num_attention_heads"], qk_head_dim(cfg), \
        cfg["v_head_dim"]
    one = 2.0 * batch * h * seq_len * seq_len / 2       # flops a unit width
    flops = one * ((d + dv) + (3 * d + 2 * dv))
    rows = batch * seq_len * itemsize
    q, o = rows * h * d, rows * h * dv
    k = rows * (h * cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"])
    return flops, (q + k + 2 * o) + (2 * q + 2 * k + 4 * o)


def moe_train_bytes(cfg, tokens, master_itemsize=4, stream_itemsize=2):
    """Least HBM traffic of one expert layer's forward + backward: its
    weights read once in each pass and their gradient written once
    (master precision), the layer's input and output and their two
    gradients (the residual stream's precision)."""
    weights = sum(ffn_params(cfg, "E").values())
    return 3 * weights * master_itemsize \
        + 4 * tokens * cfg["hidden_size"] * stream_itemsize


def moe_roofline_pct(cfg, tokens, seconds, peaks, slots_here=None):
    """(share in %, which bound) of the roofline for all expert layers of
    one step (the main ``E`` blocks and the prediction module's): the
    least time the chip could take for their model flops — router, gated
    shared expert, the held gated experts on ``slots_here`` slots a token
    — and least bytes, forward + backward, over ``seconds``, the device
    time the step spent in them (recomputation in the time and not in the
    flops, so the share cannot pass 100)."""
    from benchmark import kernel_costs
    layers = sum(k in ("E", "P") for k in layer_kinds(cfg))
    flops = 3.0 * layers * tokens * ffn_forward_flops_per_token(
        cfg, "E", slots_here)
    return kernel_costs.roofline_share_pct(
        flops, layers * moe_train_bytes(cfg, tokens), seconds, peaks)
