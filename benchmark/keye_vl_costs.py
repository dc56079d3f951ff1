"""Parameters, model flops and least HBM bytes of a ``keye_vl``
configuration (the language model of Keye-VL-2.0: grouped-query attention
over a learned selection of keys, soft-max routed experts) under causal
training in the sparse stage, from its sizes alone: what the cell's MFU
note and its three roofline shares divide by. Kept with the benchmark (see
``kernel_costs.py``) so that no later PR moves the yardstick.

Model flops follow the MFU convention: what the forward and the backward
pass need (2 flops a multiply-add; backward twice the forward),
recomputation and the optimizer not counted. Attention reads the SELECTED
(row, key) pairs and no others, whatever tiles a kernel walks to find
them; the index scores are needed over every CAUSAL pair (a key is chosen
by comparing it), forward only (the selection has no gradient); the
indexer's loss sends a gradient back through the index scores of the
selected pairs alone.

``cfg`` is the configuration as run (benchmark/configs/<name>.json):
``num_experts`` counts the experts held here, ``num_experts_published``
the router's width, ``num_hidden_layers`` the layers held.
"""
from benchmark import kernel_costs, sdar_moe_costs

attention_params = sdar_moe_costs.attention_params
expert_params = sdar_moe_costs.expert_params
moe_forward_flops_per_row = sdar_moe_costs.moe_forward_flops_per_row


def indexer_params(cfg):
    """{part: parameters} of one layer's indexer; its layer norm's scale
    and bias under ``vectors``."""
    d, sa = cfg["hidden_size"], cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {"indexer_q": d * hi * di, "indexer_k": d * di,
            "indexer_w": d * hi, "vectors": 2 * di}


def layer_params(cfg):
    """{part: parameters} of one block as held here."""
    return dict(sdar_moe_costs.layer_params(cfg),
                indexer=sum(indexer_params(cfg).values()))


def total_params(cfg):
    """Everything held here: the blocks, the embedding slice, the untied
    head slice and the final norm."""
    return cfg["num_hidden_layers"] * sum(layer_params(cfg).values()) \
        + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"]


def causal_pairs(seq_len):
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len, top_k):
    """(row, key) pairs a token-level top-``top_k`` selection keeps of one
    sequence's causal pairs, no two scores equal: a row with no more than
    ``top_k`` causal keys keeps them all, a later row exactly ``top_k``."""
    if seq_len <= top_k:
        return causal_pairs(seq_len)
    return causal_pairs(top_k) + (seq_len - top_k) * top_k


def index_score_flops(cfg, pairs):
    """One product of the index scores over ``pairs`` (row, key) pairs:
    ``indexer_num_heads`` dots of ``indexer_head_dim``."""
    sa = cfg["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"] * pairs


def forward_flops_per_token(cfg, seq_len):
    """{part: forward flops a token}: ``scores`` the two products of
    attention over the selected pairs, ``index_scores`` over the causal
    pairs."""
    a, ix = attention_params(cfg), indexer_params(cfg)
    layers, hd = cfg["num_hidden_layers"], cfg["head_dim"]
    top_k = cfg["sa_config"]["topk"]
    return {
        "projections": layers * 2 * (sum(a.values()) - a["vectors"]),
        "indexer_projections": layers * 2 * (sum(ix.values())
                                             - ix["vectors"]),
        "scores": layers * cfg["num_attention_heads"] * 2 * 2 * hd
        * selected_pairs(seq_len, top_k) / seq_len,
        "index_scores": layers * index_score_flops(
            cfg, causal_pairs(seq_len)) / seq_len,
        "moe": layers * moe_forward_flops_per_row(cfg),
        "head": 2.0 * cfg["hidden_size"] * cfg["vocab_size"]}


def train_flops_per_token(cfg, seq_len):
    """Model flops a token of a training step: forward plus backward,
    the backward twice the forward but for the index scores, whose forward
    is over the causal pairs and whose backward (the indexer's loss's
    gradient, two products) over the selected ones."""
    f = forward_flops_per_token(cfg, seq_len)
    top_k = cfg["sa_config"]["topk"]
    index_back = cfg["num_hidden_layers"] * 2 * index_score_flops(
        cfg, selected_pairs(seq_len, top_k)) / seq_len
    return 3.0 * (sum(f.values()) - f["index_scores"]) \
        + f["index_scores"] + index_back


def _rows_bytes(cfg, seq_len, batch, itemsize):
    """(q-side bytes, kv-side bytes) of one [rows, heads, head_dim] array
    by query head and by key/value head."""
    rows = batch * seq_len * itemsize * cfg["head_dim"]
    return (rows * cfg["num_attention_heads"],
            rows * cfg["num_key_value_heads"])


def selected_flash_costs(cfg, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of the attention kernels of one layer under
    the selection, forward + backward: two products forward and five
    backward (``kernel_costs.flash_attention_flops``'s count) of ``2 d``
    flops a SELECTED pair and head, the mathematics' work whatever walks
    it. Bytes: Q, O, dO, dQ by query head, K, V, dK, dV by key/value head,
    each once a pass as ``sdar_moe_costs.attention_kernel_costs`` counts
    them, and the selection's ``seq_len^2`` int8 read once a pass."""
    top_k = cfg["sa_config"]["topk"]
    pairs = batch * selected_pairs(seq_len, top_k)
    flops = 7 * 2.0 * cfg["num_attention_heads"] * pairs * cfg["head_dim"]
    q, k = _rows_bytes(cfg, seq_len, batch, itemsize)
    return flops, (2 * q + 2 * k) + (4 * q + 4 * k) \
        + 2 * batch * seq_len * seq_len


def _indexer_bytes(cfg, seq_len, batch, itemsize):
    """qI, kI (``itemsize``) and w (float32) once."""
    sa = cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return batch * seq_len * ((hi * di + di) * itemsize + hi * 4)


def select_kernel_costs(cfg, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of one layer's selection: the index scores
    of every causal pair (the threshold's counting is no matrix work);
    reads qI, kI and w, writes the ``seq_len^2`` int8 selection and three
    float32 a row."""
    return (batch * index_score_flops(cfg, causal_pairs(seq_len)),
            _indexer_bytes(cfg, seq_len, batch, itemsize)
            + batch * seq_len * (seq_len + 3 * 4))


def kl_kernel_costs(cfg, seq_len, batch=1, itemsize=2):
    """(flops, least HBM bytes) of one layer's pass of the indexer's loss
    over the SELECTED pairs: the heads' scores again (one product of ``2
    d`` a pair and head: their probabilities come from the kept
    statistics), the index scores again and their two backward products.
    Reads q, k (by key/value head), two statistics a row and head, the
    selection, qI, kI, w and lse; writes the three gradients."""
    top_k = cfg["sa_config"]["topk"]
    pairs = batch * selected_pairs(seq_len, top_k)
    flops = 2.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs \
        + 3 * index_score_flops(cfg, pairs)
    q, k = _rows_bytes(cfg, seq_len, batch, itemsize)
    stats = batch * seq_len * (2 * cfg["num_attention_heads"] + 1) * 4
    return flops, q + k + stats + batch * seq_len * seq_len \
        + 2 * _indexer_bytes(cfg, seq_len, batch, itemsize)


def roofline_pct(kernel_costs_of, cfg, traffic, seconds, peaks, calls=None):
    """The share in % of their roofline that kernels which took ``seconds``
    a step hold: ``kernel_costs_of`` (one of the three functions above)
    over the ``calls`` of a step that the time covers, one a layer where
    none is given."""
    calls = cfg["num_hidden_layers"] if calls is None else calls
    flops, nbytes = kernel_costs_of(cfg, traffic["seq_len"],
                                    traffic["batch_per_chip"])
    return kernel_costs.roofline_share_pct(
        calls * flops, calls * nbytes, seconds, peaks)[0]
