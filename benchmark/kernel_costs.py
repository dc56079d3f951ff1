"""Operations and bytes that an algorithm needs, from its shapes alone,
and the table of peaks. Kept with the benchmark so that no later PR can
move the yardstick: a roofline share is (least time the chip could take)
/ (time the kernel took), the least time being the larger of
flops / peak flops and bytes / peak bytes per second.

Model flops follow the MFU convention: what forward and backward need,
recomputation and the optimizer not counted.
"""
import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks_for_kind(kind):
    """The peaks of one chip of ``kind``; raises on a kind not in
    peaks.json."""
    with open(_PEAKS_FILE) as f:
        kinds = json.load(f)["kinds"]
    if kind not in kinds:
        raise KeyError(f"device kind {kind!r} is not in benchmark/peaks.json "
                       f"(known: {sorted(kinds)})")
    return kinds[kind]


# --- whole models ----------------------------------------------------------

def bert_matmul_params(cfg):
    """Weights that every token multiplies: the encoder's four d x d and two
    d x f matrices per layer, the masked-LM transform and the tied d x V
    output matrix. Embedding look-ups, the pooler and the next-sentence
    head (once per sequence) are not matrix work per token."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return (cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f)
            + d * d + cfg["vocab_size"] * d)


def transformer_train_flops_per_token(matmul_params, layers, hidden, seq):
    """6 flops per weight per token (2 forward, 4 backward: Kaplan et al.,
    arXiv:2001.08361, table 1) plus attention's two S x S products per
    layer: 2 * 2 * S * d forward, three times that with the backward."""
    return 6.0 * matmul_params + 12.0 * layers * seq * hidden


# ResNet-50 at 224 x 224: 4.1e9 multiply-adds forward (He et al.,
# arXiv:1512.03385, table 1: "3.8e9 FLOPs" counts multiply-adds of the
# convolutions only; 4.1e9 with the stem's and the classifier's, the figure
# monitor/step.py carries). Two flops a multiply-add, backward twice forward.
RESNET50_TRAIN_FLOPS_PER_IMAGE = 3 * 2 * 4.1e9


# --- kernels ---------------------------------------------------------------

def layer_norm_bytes(rows, hidden, itemsize, backward):
    """Least HBM traffic of a row-wise layer norm. Forward reads x and
    writes y (gamma, beta and the two f32 statistics per row are noise but
    counted). Backward reads x and dy, writes dx, re-reads the statistics
    and writes one gamma and one beta gradient."""
    body = rows * hidden * itemsize
    stats = rows * 2 * 4
    vec = hidden * 4
    if backward:
        return 3 * body + stats + 3 * vec
    return 2 * body + stats + 2 * vec


def flash_attention_flops(batch, heads, seq_q, seq_k, head_dim, backward):
    """Matrix flops of exact attention. Forward: QK^T and PV, 2 flops a
    multiply-add. Backward (Dao et al., arXiv:2205.14135, algorithm 4):
    recompute QK^T, then dV, dP, dQ and dK: five products."""
    one = 2.0 * batch * heads * seq_q * seq_k * head_dim
    return 5 * one if backward else 2 * one


def flash_attention_bytes(batch, heads, seq_q, seq_k, head_dim, itemsize,
                          backward):
    """Least HBM traffic: forward reads Q, K, V and writes O; backward
    reads Q, K, V, O, dO and writes dQ, dK, dV. The S x S scores never
    reach HBM, which is the point of the kernel."""
    q = batch * heads * seq_q * head_dim * itemsize
    kv = batch * heads * seq_k * head_dim * itemsize
    if backward:
        return 3 * q + 2 * kv + q + 2 * kv      # Q, O, dO, K, V; dQ, dK, dV
    return 2 * q + 2 * kv


def roofline_share_pct(flops, nbytes, seconds, peaks):
    """(share in %, which bound): least time over the time taken."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    least = max(t_flops, t_bytes)
    return 100.0 * least / seconds, "flops" if t_flops >= t_bytes else "bytes"
