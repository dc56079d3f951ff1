"""Parameters, model flops and least HBM bytes of a ``nemotron_h``
configuration, from its sizes alone: what the new cell's MFU note and
its ``mamba_roofline`` / ``moe_roofline`` divide by. Kept with the
benchmark (see ``kernel_costs.py``) so that no later PR moves the
yardstick.

Model flops follow the MFU convention: what the forward and the backward
pass need (2 flops a multiply-add; backward twice the forward),
recomputation and the optimizer not counted. Sequence mixing is counted
as the algorithm needs it: causal attention reads half of the S x S
products; the state-space recurrence is counted in its chunked form (the
form every implementation of Mamba-2 evaluates), whose cost is linear in
the sequence.

``cfg`` is the configuration as run (benchmark/configs/<name>.json):
``n_routed_experts`` counts the experts held here and
``n_routed_experts_published`` the router's width.
"""

KINDS = ("M", "*", "E")


def mamba_widths(cfg):
    """(inner width, conv width, in_proj's output width)."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, conv, inner + conv + cfg["mamba_num_heads"]


def layer_params(cfg, kind):
    """{part: parameters} of one block of ``kind`` as held here, its
    pre-norm included under ``vectors``."""
    d = cfg["hidden_size"]
    if kind == "M":
        inner, conv, proj = mamba_widths(cfg)
        heads = cfg["mamba_num_heads"]
        return {"in_proj": d * proj, "out_proj": inner * d,
                "conv": conv * cfg["conv_kernel"] + conv,
                "vectors": 3 * heads + inner + d}
    if kind == "*":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        kv = cfg["num_key_value_heads"] * cfg["head_dim"]
        return {"q_proj": d * q, "k_proj": d * kv, "v_proj": d * kv,
                "o_proj": q * d, "vectors": d}
    if kind == "E":
        return {"router": d * cfg["n_routed_experts_published"],
                "shared": 2 * d * cfg["moe_shared_expert_intermediate_size"],
                "routed": cfg["n_routed_experts"] * expert_params(cfg),
                "vectors": d}
    raise ValueError(f"layer kind {kind!r} is not one of {KINDS}")


def expert_params(cfg):
    """One routed expert: an up and a down matrix, no gate."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def total_params(cfg):
    """Everything held here: the blocks, the embedding slice, the untied
    head slice and the final norm."""
    blocks = sum(sum(layer_params(cfg, k).values())
                 for k in cfg["hybrid_override_pattern"])
    return blocks + 2 * cfg["vocab_size"] * cfg["hidden_size"] \
        + cfg["hidden_size"]


def slots_here_per_token(cfg):
    """Expected (token, choice) slots a token routes to the experts held
    here, under a router that spreads evenly."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / cfg["n_routed_experts_published"]


def layer_forward_flops_per_token(cfg, kind, seq_len, slots_here=None):
    """Forward flops one token costs in one block of ``kind``.
    ``slots_here``: slots a token routes to the held experts (default:
    the expectation)."""
    d = cfg["hidden_size"]
    p = layer_params(cfg, kind)
    if kind == "M":
        inner, conv, _ = mamba_widths(cfg)
        chunk, state = cfg["chunk_size"], cfg["ssm_state_size"]
        scan = (2 * chunk * state * cfg["n_groups"]   # C . B inside a chunk
                + 2 * chunk * inner                   # (L o CB) x
                + 2 * state * inner                   # the chunk's state
                + 2 * state * inner)                  # C . carried state
        return 2 * (p["in_proj"] + p["out_proj"]) + scan \
            + 2 * cfg["conv_kernel"] * conv
    if kind == "*":
        q = cfg["num_attention_heads"] * cfg["head_dim"]
        weights = 2 * (p["q_proj"] + p["k_proj"] + p["v_proj"] + p["o_proj"])
        return weights + 2 * 2 * seq_len * q / 2      # QK^T and PV, causal
    if kind == "E":
        if slots_here is None:
            slots_here = slots_here_per_token(cfg)
        return 2 * (p["router"] + p["shared"]) \
            + 2 * slots_here * expert_params(cfg)
    raise ValueError(f"layer kind {kind!r} is not one of {KINDS}")


def forward_flops_per_token(cfg, seq_len):
    """{kind or "head": forward flops a token, all blocks of the kind}."""
    out = {k: 0.0 for k in KINDS}
    for kind in cfg["hybrid_override_pattern"]:
        out[kind] += layer_forward_flops_per_token(cfg, kind, seq_len)
    out["head"] = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    return out


def train_flops_per_token(cfg, seq_len):
    """Model flops a token of a training step: forward plus backward."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def layer_train_bytes(cfg, kind, tokens, master_itemsize=4,
                      stream_itemsize=2):
    """Least HBM traffic of one block's forward + backward: its weights
    read once in each pass and their gradient written once (master
    precision), the block's input and output and their two gradients
    (the residual stream's precision). Activations inside the block are
    the implementation's to keep on chip or not."""
    weights = sum(layer_params(cfg, kind).values())
    stream = tokens * cfg["hidden_size"] * stream_itemsize
    return 3 * weights * master_itemsize + 4 * stream


def attention_kernel_costs(cfg, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of the attention kernels of one ``*``
    block, forward + backward: causal, so half of the S x S products of
    exact attention (forward QK^T and PV; backward, Dao et al.
    arXiv:2205.14135 algorithm 4, five products: ``kernel_costs.
    flash_attention_flops`` halved); Q, O, dO and dQ by query head, K, V,
    dK and dV by key/value head — each K/V head crosses once, whatever an
    implementation repeats."""
    heads, d = cfg["num_attention_heads"], cfg["head_dim"]
    one = 2.0 * batch * heads * seq_len * seq_len * d / 2
    q = batch * heads * seq_len * d * itemsize
    kv = batch * cfg["num_key_value_heads"] * seq_len * d * itemsize
    return (2 + 5) * one, (2 * q + 2 * kv) + (4 * q + 4 * kv)


def kind_roofline_pct(cfg, kind, seq_len, tokens, seconds, peaks,
                      slots_here=None):
    """(share in %, which bound) of the roofline for all blocks of
    ``kind`` in one step: the least time the chip could take for their
    model flops and least bytes, forward + backward, over ``seconds``,
    the device time the step spent in them (recomputation included in
    the time and not in the flops, so the share cannot pass 100)."""
    from benchmark import kernel_costs
    layers = cfg["hybrid_override_pattern"].count(kind)
    flops = 3.0 * layers * tokens * layer_forward_flops_per_token(
        cfg, kind, seq_len, slots_here)
    nbytes = layers * layer_train_bytes(cfg, kind, tokens)
    return kernel_costs.roofline_share_pct(flops, nbytes, seconds, peaks)
