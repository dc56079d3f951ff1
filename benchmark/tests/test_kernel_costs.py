"""Byte and flop counts at BERT-base shapes, worked by hand."""
import json
import os

import pytest

from benchmark import kernel_costs as K

HERE = os.path.dirname(os.path.abspath(__file__))
BERT = json.load(open(os.path.join(HERE, "..", "configs", "bert_base.json")))


def test_peaks_lookup_knows_the_v5e_and_raises_on_anything_else():
    assert K.peaks_for_kind("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert K.peaks_for_kind("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", "TPU v5", "TPU v5 lite pod", ""):
        with pytest.raises(KeyError):
            K.peaks_for_kind(kind)


def test_bert_base_matmul_weights_and_flops_per_token():
    # per layer 4 * 768^2 + 2 * 768 * 3072 = 2,359,296 + 4,718,592
    # twelve layers 84,934,656; + 768^2 = 589,824; + 30522 * 768 = 23,440,896
    assert K.bert_matmul_params(BERT) == 84_934_656 + 589_824 + 23_440_896
    n = K.bert_matmul_params(BERT)
    # attention at seq 128: 12 * 12 layers * 128 * 768 = 14,155,776
    assert K.transformer_train_flops_per_token(n, 12, 768, 128) == \
        6 * 108_965_376 + 14_155_776
    assert K.transformer_train_flops_per_token(n, 12, 768, 512) == \
        6 * 108_965_376 + 4 * 14_155_776


def test_layer_norm_bytes_at_8192_rows_of_768_bf16():
    body = 8192 * 768 * 2                     # 12,582,912
    assert K.layer_norm_bytes(8192, 768, 2, backward=False) == \
        2 * body + 8192 * 8 + 2 * 768 * 4     # x in, y out, stats, gamma, beta
    assert K.layer_norm_bytes(8192, 768, 2, backward=True) == \
        3 * body + 8192 * 8 + 3 * 768 * 4     # x, dy in, dx out, stats, g/dg/db


def test_flash_counts_at_16_x_12_heads_x_512_x_64_bf16():
    one = 2 * 16 * 12 * 512 * 512 * 64        # 6,442,450,944
    assert K.flash_attention_flops(16, 12, 512, 512, 64, False) == 2 * one
    assert K.flash_attention_flops(16, 12, 512, 512, 64, True) == 5 * one
    t = 16 * 12 * 512 * 64 * 2                # one of Q, K, V, O: 12,582,912
    assert K.flash_attention_bytes(16, 12, 512, 512, 64, 2, False) == 4 * t
    assert K.flash_attention_bytes(16, 12, 512, 512, 64, 2, True) == 8 * t


def test_roofline_share_names_the_larger_bound():
    peaks = K.peaks_for_kind("TPU v5 lite")
    # 25,231,360 bytes at 819 GB/s = 30.807 us; taken 61.614 us -> 50 %
    nbytes = K.layer_norm_bytes(8192, 768, 2, False)
    share, bound = K.roofline_share_pct(0.0, nbytes, 2 * nbytes / 819e9, peaks)
    assert bound == "bytes" and share == pytest.approx(50.0)
    flops = K.flash_attention_flops(16, 12, 512, 512, 64, False)
    share, bound = K.roofline_share_pct(
        flops, K.flash_attention_bytes(16, 12, 512, 512, 64, 2, False),
        4 * flops / 197e12, peaks)
    assert bound == "flops" and share == pytest.approx(25.0)
