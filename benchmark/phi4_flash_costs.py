"""Parameters, model flops and least HBM bytes of a ``phi4_flash``
configuration under causal pre-training, from its sizes alone: what the
cell's MFU note, its ``sscan_roofline`` and its two flash rooflines divide
by. Kept with the benchmark (see ``kernel_costs.py``) so that no later PR
moves the yardstick.

Model flops follow the MFU convention: what the forward and the backward
pass need (2 flops a multiply-add; backward twice the forward),
recomputation and the optimizer not counted. The selective scan is counted
at ``9 N`` flops a (token, channel) forward: an exponential's argument,
the exponential, the decay, the input's product with ``B``, its sum, the
product with ``C`` and its sum, and two for what does not depend on the
state; they are vector flops, and ``peaks.json`` has no vector peak: see
``sscan_roofline_pct``.

``cfg`` is the configuration as run (benchmark/configs/<name>.json):
``num_hidden_layers`` counts the layers held, the source's ``first_layer ..
first_layer + num_hidden_layers`` of ``num_hidden_layers_published``;
``vocab_size`` the rows of the vocabulary held. ``published(cfg)`` is the
whole model's configuration.
"""
from benchmark import kernel_costs
from benchmark.reference.phi4_flash import (           # noqa: F401
    head_dim, layer_kinds, mamba_sizes)


def mamba_params(cfg):
    """{part: parameters} of one Mamba-1 mixer."""
    d = cfg["hidden_size"]
    inner, n, taps, r = mamba_sizes(cfg)
    return {"in_proj": d * 2 * inner, "taps": inner * taps,
            "conv_bias": inner, "x_proj": inner * (r + 2 * n),
            "dt_proj": r * inner + inner, "A_log": inner * n, "D": inner,
            "out_proj": inner * d}


def attention_params(cfg, cross=False):
    """{part: parameters} of one differential attention layer, with its
    biases; a cross attention makes queries only."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    parts = {"q_proj": d * heads * hd + heads * hd,
             "o_proj": heads * hd * d + d, "lambdas": 4 * hd,
             "pair_norm": 2 * hd}
    if not cross:
        parts["kv_proj"] = 2 * (d * kv * hd + kv * hd)
    return parts


def memory_unit_params(cfg):
    inner = mamba_sizes(cfg)[0]
    return {"in_proj": cfg["hidden_size"] * inner,
            "out_proj": inner * cfg["hidden_size"]}


def mlp_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mixer_params(cfg, kind):
    if kind == "mamba":
        return mamba_params(cfg)
    if kind == "memory_unit":
        return memory_unit_params(cfg)
    return attention_params(cfg, cross=kind == "cross_attention")


def layer_params(cfg, kind):
    """Parameters of one block: mixer, feed-forward, two layer norms with
    scale and bias."""
    return sum(mixer_params(cfg, kind).values()) + mlp_params(cfg) \
        + 4 * cfg["hidden_size"]


def total_params(cfg):
    """Everything held here: the blocks, the embedding slice (which is the
    head) and the final norm."""
    return sum(layer_params(cfg, kind) for kind, _, _ in layer_kinds(cfg)) \
        + cfg["vocab_size"] * cfg["hidden_size"] + 2 * cfg["hidden_size"]


def published(cfg):
    """The configuration of the whole model ``cfg`` is a share of."""
    return dict(cfg, first_layer=0,
                num_hidden_layers=cfg.get("num_hidden_layers_published",
                                          cfg["num_hidden_layers"]),
                vocab_size=cfg.get("vocab_size_published",
                                   cfg["vocab_size"]))


def kinds_held(cfg):
    """{kind: layers of it held here}."""
    out = {}
    for kind, _, _ in layer_kinds(cfg):
        out[kind] = out.get(kind, 0) + 1
    return out


def causal_pairs(seq_len):
    """(row, key) pairs of one sequence a head may read: ``j <= i``."""
    return seq_len * (seq_len + 1) // 2


def window_pairs(seq_len, window):
    """Pairs under a sliding window: row i reads ``min(i + 1, window)``."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def layer_pairs(cfg, kind, seq_len):
    if kind == "window_attention":
        return window_pairs(seq_len, cfg["sliding_window"])
    return causal_pairs(seq_len)


SCAN_FLOPS = 9          # a (token, channel, state) forward


def forward_flops_per_token(cfg, seq_len):
    """{part: forward flops a token}: the matrices of each kind of mixer,
    ``scan`` the recurrences, ``scores`` the two products of attention
    (scores 64 wide, values 128) over the pairs each layer allows."""
    held = kinds_held(cfg)
    hd, heads = head_dim(cfg), cfg["num_attention_heads"]
    inner, n, taps, _ = mamba_sizes(cfg)
    m = mamba_params(cfg)
    out = {
        "mamba": held.get("mamba", 0) * (2 * (
            m["in_proj"] + m["x_proj"] + m["dt_proj"] - inner
            + m["out_proj"]) + 2 * m["taps"]),
        "scan": held.get("mamba", 0) * SCAN_FLOPS * inner * n,
        "memory_unit": held.get("memory_unit", 0) * 2 * sum(
            memory_unit_params(cfg).values()),
        "mlp": len(layer_kinds(cfg)) * 2 * mlp_params(cfg),
        "head": 2.0 * cfg["hidden_size"] * cfg["vocab_size"],
        "attention": 0, "scores": 0.0}
    for kind in ("window_attention", "full_attention", "cross_attention"):
        a = attention_params(cfg, cross=kind == "cross_attention")
        layers = held.get(kind, 0)
        out["attention"] += layers * 2 * (
            a["q_proj"] + a["o_proj"] + a.get("kv_proj", 0))
        # QK^T at D, PV at 2 D
        out["scores"] += layers * heads * layer_pairs(cfg, kind, seq_len) \
            / seq_len * 2 * 3 * hd
    return out


def train_flops_per_token(cfg, seq_len):
    """Model flops a token of a training step: forward plus backward."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq_len).values())


def scan_kernel_costs(cfg, traffic, itemsize=2):
    """(flops, least HBM bytes) of the selective scan of ONE Mamba layer,
    forward + backward. With ``n = B S inner itemsize`` the forward reads
    ``x``, the step sizes and ``z`` and writes ``y`` and the gated result
    (5 n; layer N/2's memory IS ``y``); the backward reads those three,
    the two results' gradients and writes three gradients (8 n). ``B``,
    ``C`` and their gradients are ``B S 2 N`` values each way. Flops: the
    forward's ``SCAN_FLOPS`` a (token, channel, state), the backward three
    times that (its states made again, then the walk back)."""
    inner, n_state, _, _ = mamba_sizes(cfg)
    rows = traffic["batch_per_chip"] * traffic["seq_len"]
    n = rows * inner * itemsize
    small = rows * 2 * n_state * itemsize
    flops = 4.0 * SCAN_FLOPS * rows * inner * n_state
    return flops, (5 * n + small) + (8 * n + 2 * small)


def sscan_roofline_pct(cfg, traffic, seconds, peaks):
    """Share of the chip's roofline of the step's selective-scan kernels
    that took ``seconds`` of device time a step: one forward and one
    backward call a Mamba layer (a recomputed forward kernel is in the time
    and not in the work). ``peaks.json`` has no vector-unit peak, so the
    least time is of HBM bytes and of flops AT THE MXU'S RATE: the kernels'
    own bound is the vector and transcendental units, and the share reads
    low by construction. None where no Mamba layer is held."""
    layers = kinds_held(cfg).get("mamba", 0)
    if not layers:
        return None
    flops, nbytes = scan_kernel_costs(cfg, traffic)
    return kernel_costs.roofline_share_pct(
        layers * flops, layers * nbytes, seconds, peaks)[0]


def attention_kernel_costs(cfg, kind, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of the attention kernels of ONE layer of
    ``kind``, forward + backward, over the pairs it allows with queries
    and keys ``D`` wide and values ``2 D``: forward QK^T (``2 D`` flops a
    pair and head) and PV (``4 D``); backward QK^T again, dP = dO V^T, dV
    = P^T dO (``4 D`` each), dQ = dS K, dK = dS^T Q (``2 D``): ``22 D`` in
    all. Bytes, each operand once: Q and dQ (``D``), O and dO (``2 D``) by
    query head; K, dK by key head (``D``), V, dV by value pair (``2 D``),
    whatever an implementation repeats: the forward reads Q, K, V and
    writes O, the backward reads Q, K, V, O, dO and writes dQ, dK, dV."""
    hd = head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    flops = 22.0 * hd * batch * heads * layer_pairs(cfg, kind, seq_len)
    rows = batch * seq_len * itemsize
    q, o = rows * heads * hd, rows * heads * 2 * hd
    k, v = rows * kv * hd, rows * (kv // 2) * 2 * hd
    return flops, (q + k + v + o) + (q + k + v + 2 * o + q + k + v)


def flash_roofline_pct(cfg, traffic, kinds, seconds, peaks):
    """Share of their roofline of the flash kernels of the step's layers of
    ``kinds`` that took ``seconds`` of device time a step; None where none
    is held."""
    held = kinds_held(cfg)
    flops = nbytes = 0.0
    for kind in kinds:
        f, b = attention_kernel_costs(cfg, kind, traffic["seq_len"],
                                      traffic["batch_per_chip"])
        flops += held.get(kind, 0) * f
        nbytes += held.get(kind, 0) * b
    if not flops:
        return None
    return kernel_costs.roofline_share_pct(flops, nbytes, seconds, peaks)[0]
