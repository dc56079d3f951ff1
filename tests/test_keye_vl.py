"""The language model of Keye-VL-2.0 in the sparse stage, at a tiny size on
the CPU, against the benchmark's plain float32 reference (benchmark/
reference/keye_vl.py, which imports nothing of paddle_tpu): the learned
selection (a scored indexer, the ``topk`` best keys a row as a threshold),
attention under it, the indexer's own loss beside the language-model loss
with disjoint gradients, rotary positions of three axes, the soft-max
router and the chip's share of the experts, and the model trained through
``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``.
The contract with the reference is tests/family_contract.py's; the three
ops' own cases are in tests/test_sparse_attention.py.
"""
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                         # noqa: E402
from paddle_tpu import monitor, nn                              # noqa: E402
from paddle_tpu.models.keye_vl import (                         # noqa: E402
    KeyeVL2ForCausalLM, KeyeVL2TextConfig)
from paddle_tpu.nn import functional as F                       # noqa: E402
from benchmark.reference import keye_vl as R                    # noqa: E402
from family_contract import (Family, Reference,                 # noqa: E402
                             check_expert_shares_add_up,
                             check_matches_reference,
                             check_trains_through_to_static,
                             plain as _plain, routed_share)

SEQ = 24


def _positions(seq=SEQ):
    """Three rows that differ: text, then a 3 x 4 image span from position
    6 (``get_rope_index``'s rule), then text."""
    at = np.zeros((3, seq), np.int32)
    at[:, :6] = np.arange(6)
    row, col = np.divmod(np.arange(12), 4)
    at[:, 6:18] = 6 + np.stack([0 * row, row, col])
    at[:, 18:] = 6 + 4 + np.arange(seq - 18)
    return at


def _batch(rows=2, seq=SEQ, vocab=256, seed=0):
    """(ids, position ids [3, seq], label weights): weight 0 where the next
    position lies in the span and at the last position."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (rows, seq)).astype(np.int32)
    weights = np.ones((rows, seq), np.float32)
    weights[:, 5:17] = 0.0
    weights[:, -1] = 0.0
    return ids, _positions(seq), weights


# the forward reads the ids and the positions; the loss the ids, the
# weights and the forward's second result
FAMILY = Family(
    R, KeyeVL2ForCausalLM, KeyeVL2TextConfig.tiny,
    batch=lambda seed: _batch(seed=seed),
    inputs=lambda batch: batch[:2],
    loss=lambda model, outputs, batch: model.loss(outputs[0], batch[0],
                                                  batch[2], outputs[1]),
    outputs=("logits", "indexer_loss"))


@pytest.fixture(scope="module")
def reference():
    return Reference(FAMILY)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_both_losses_and_every_gradient(
        reference, recompute):
    seen = check_matches_reference(reference, recompute)
    assert tuple(seen.outputs[0].shape) == (2, SEQ, 256)
    # L_I is a loss of its own, above zero at random weights
    assert float(seen.outputs[1].numpy()) > 0.01
    lm, li = R.losses(seen.cfg, seen.weights,
                      tuple(jnp.asarray(a) for a in seen.batch))
    assert abs(float(seen.outputs[1].numpy()) - float(li)) < 1e-6
    assert abs(float(seen.loss.numpy()) - float(lm + li)) < 1e-5
    # the indexer's four leaves a layer are among the compared ones
    leaves = R.compared_leaves(seen.cfg)
    assert len(leaves) == 2 + 2 * (8 + 4)
    for part in ("indexer_q.weight", "indexer_k.weight", "indexer_w.weight",
                 "indexer_k_norm.weight"):
        assert f"layers.1.self_attn.{part}" in leaves


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference(
        reference):
    monitor.device_counters.reset()     # the model registers after it
    # bf16 products against float32: the losses agree to bf16's rounding
    check_trains_through_to_static(reference, rtol=3e-3)
    # every layer counted its pairs on the device: 8 keys a row of 24
    seen = monitor.device_counters.read("dsa.")
    causal = SEQ * (SEQ + 1) // 2
    assert seen["dsa.pairs_causal"] == 3 * 2 * 2 * causal
    # bfloat16 operands make equal scores, and a tie keeps both keys
    exact = 3 * 2 * 2 * (8 * 9 // 2 + (SEQ - 8) * 8)
    assert exact <= seen["dsa.pairs_selected"] < 1.02 * exact


@pytest.mark.parametrize("dropped,recompute", [("indexer_loss", True),
                                               ("lm_loss", False)])
def test_the_two_losses_gradients_are_disjoint(reference, dropped, recompute):
    """With ``L_I`` taken out of the sum no indexer leaf moves (through a
    recomputed block, which JAX differentiates whole), with ``L_LM`` taken
    out no other leaf does (through the tape): each leaf's gradient is one
    loss's alone."""
    model, _, _ = reference.model(recompute=recompute)
    ids, at, weights = (pt.to_tensor(a) for a in _batch())
    logits, indexer_loss = model(ids, at)
    lm = model.loss(logits, ids, weights, indexer_loss) - indexer_loss
    (lm if dropped == "indexer_loss" else indexer_loss).backward()
    for name, p in model.named_parameters():
        moved = p._grad is not None and float(jnp.abs(p._grad).max()) > 0.0
        assert moved == (("indexer" in name) == (dropped == "lm_loss")), name


def test_config_checks_the_share_the_sections_and_the_names():
    c = KeyeVL2TextConfig()
    assert (c.num_experts_published, c.mrope_section, c.rope_theta) == \
        (128, (16, 24, 24), 1e7)
    assert (c.sa_config["indexer_num_heads"], c.sa_config["topk"]) == \
        (16, 2048)
    with pytest.raises(ValueError, match="published"):
        KeyeVL2TextConfig.tiny(first_expert_held=14)
    with pytest.raises(ValueError, match="mrope_section"):
        KeyeVL2TextConfig.tiny(rope_scaling={"mrope_section": [2, 3, 4]})
    with pytest.raises(ValueError, match="sa_config"):
        KeyeVL2TextConfig.tiny(sa_config={"topk": 8})
    names = dict(KeyeVL2ForCausalLM(KeyeVL2TextConfig.tiny())
                 .named_parameters())
    for name in ("layers.0.self_attn.indexer_q.weight",
                 "layers.0.self_attn.indexer_k.weight",
                 "layers.0.self_attn.indexer_k_norm.weight",
                 "layers.0.self_attn.indexer_k_norm.bias",
                 "layers.1.self_attn.indexer_w.weight",
                 "layers.0.self_attn.q_norm.weight",
                 "layers.1.mlp.router.weight", "lm_head.weight"):
        assert name in names, name
    assert tuple(names["layers.0.self_attn.indexer_q.weight"].shape) == \
        (64, 4 * 8)
    assert pt.models.KeyeVL2TextConfig is KeyeVL2TextConfig


def test_the_configuration_files_widths_are_the_catalog_rows():
    """Every number of the published language-model config stands in the
    benchmark's file under its key, but the three cuts it lists."""
    published = {
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 262144,
        "max_window_layers": 48, "mlp_only_layers": [],
        "model_type": "KeyeVL2", "moe_intermediate_size": 768,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 4,
        "num_local_experts": 128, "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        cfg = json.load(f)
    cut = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 18992}
    assert sorted(cfg["reduced"]) == sorted(cut)
    for key, value in published.items():
        assert cfg[key] == cut.get(key, value), key
    assert (cfg["num_hidden_layers_published"], cfg["vocab_size_published"],
            cfg["num_experts_published"]) == (48, 151936, 128)


# -- three-axis positions -----------------------------------------------------

def test_three_equal_position_rows_are_the_one_axis_rotation_bit_for_bit():
    x = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(7),
                                                  (2, 4, SEQ, 16))))
    at = (np.arange(SEQ) * 3).astype(np.int32)
    one = F.rotary_embedding(x, pt.to_tensor(at), theta=1e4,
                             interleaved=False).numpy()
    three = F.rotary_embedding(x, pt.to_tensor(np.stack([at] * 3)),
                               theta=1e4, interleaved=False,
                               sections=(2, 3, 3)).numpy()
    assert np.array_equal(one, three)
    proj = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(8),
                                                     (2, SEQ, 64))))
    scale = pt.to_tensor(np.linspace(0.5, 1.5, 16).astype(np.float32))
    one = F.qk_heads(proj, 4, scale, 1e-6, pt.to_tensor(at), 1e4).numpy()
    three = F.qk_heads(proj, 4, scale, 1e-6, pt.to_tensor(np.stack([at] * 3)),
                       1e4, sections=(2, 3, 3)).numpy()
    assert np.array_equal(one, three)
    # against the reference, rows that differ
    at3 = _positions()
    got = F.rotary_embedding(x, pt.to_tensor(at3), theta=1e4,
                             interleaved=False, sections=(2, 3, 3)).numpy()
    want = R.rotary(jnp.moveaxis(x.data, 2, 0), jnp.asarray(at3), 1e4,
                    (2, 3, 3))
    np.testing.assert_allclose(got, np.moveaxis(want, 0, 2), atol=2e-6)
    with pytest.raises(ValueError, match="sections"):
        F.rotary_embedding(x, pt.to_tensor(at), theta=1e4,
                           sections=(2, 3, 3))
    with pytest.raises(ValueError, match="sections"):
        F.qk_heads(proj, 4, scale, 1e-6, pt.to_tensor(at3), 1e4,
                   sections=(2, 3, 4))


def test_qk_heads_existing_callers_trace_what_they_traced():
    """A call without sections carries no new attribute: its jaxpr is the
    one of a call that never heard of them."""
    from paddle_tpu.ops import nn_ops
    proj = np.zeros((1, SEQ, 64), np.float32)
    scale, at = np.ones(16, np.float32), np.arange(SEQ, dtype=np.int32)

    def traced(fn):
        return str(jax.make_jaxpr(fn)(proj, scale, at))

    freq = tuple(nn_ops._rotary_frequencies(16, 1e4, "t").tolist())
    direct = traced(lambda x, w, p: nn_ops._qk_heads(
        x, w, p, heads=4, epsilon=1e-6, freq=freq, normed=True,
        positioned=True))
    through = traced(lambda x, w, p: F.qk_heads(
        pt.to_tensor(x), 4, pt.to_tensor(w), 1e-6, pt.to_tensor(p),
        1e4).data)
    assert direct == through


# -- the share -----------------------------------------------------------------

def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """16 experts, top-3: the parts that 8 shares of 2 experts give add up
    to what the layer that holds all 16 gives, in the program and in the
    reference (the deployment's eight chips a layer)."""
    key = jax.random.key(21)
    u = 0.5 * jax.random.normal(key, (2, SEQ, 64))
    whole = nn.RoutedMoE(64, 32, 16, 3, gated=True, scoring="softmax")
    for i, (_, p) in enumerate(whole.named_parameters()):
        p.set_value(0.2 * jax.random.normal(jax.random.fold_in(key, i + 1),
                                            tuple(p.shape)))
    w = {k: p.data for k, p in whole.named_parameters()}

    def layer(first, n):
        return whole if n == 16 else routed_share(
            lambda held: nn.RoutedMoE(64, 32, 16, 3, gated=True,
                                      scoring="softmax", experts_held=held),
            w, first, n)

    routed = check_expert_shares_add_up(
        R, w, layer, lambda first, n: dict(
            num_experts=n, num_experts_published=16, num_experts_per_tok=3,
            first_expert_held=first),
        u, experts=16, held=2)
    assert routed == 2 * SEQ * 3        # every slot on exactly one share
