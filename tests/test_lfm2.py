"""LFM2-MoE on the training path, at a tiny size on the CPU, against the
benchmark's plain float32 reference (benchmark/reference/lfm2_moe.py,
which imports nothing of paddle_tpu): gated short-convolution layers
beside a grouped-query attention layer in one stack, the double-gated
convolution as one op with an XLA route and a kernel route, a dense layer
in front of sigmoid-routed gated experts and the chip's share of them, the
tied head, and the model trained through ``jit.to_static`` +
``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``. The contract with
the reference is tests/family_contract.py's; the convolution op's two
routes are in tests/test_gated_short_conv.py.
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
from paddle_tpu import nn                                       # noqa: E402
from paddle_tpu.models.lfm2 import (                            # noqa: E402
    Lfm2MoeConfig, Lfm2MoeForCausalLM)
from benchmark.reference import lfm2_moe as R                   # noqa: E402
from family_contract import (Family, Reference,                 # noqa: E402
                             check_adamw_step, check_expert_shares_add_up,
                             check_matches_reference,
                             check_trains_through_to_static, ids as _ids,
                             plain as _plain, rel as _rel, routed_share)

# layer_types is cut already: the reference reads it from layer 0 on
FAMILY = Family(R, Lfm2MoeForCausalLM, Lfm2MoeConfig.tiny,
                cfg_extra=dict(first_layer=0))


@pytest.fixture(scope="module")
def reference():
    return Reference(FAMILY)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        reference, recompute):
    """Five layers as the configuration cuts them: a conv layer over the
    dense feed-forward, an attention layer and three conv layers over
    experts; two sequences."""
    seen = check_matches_reference(reference, recompute)
    assert R.layer_kinds(seen.cfg) == [
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False)]
    assert tuple(seen.outputs[0].shape) == (2, 24, 256)
    # embedding, 4 x 3 of the conv operators, 4 of attention, 3 of the
    # dense layer, 4 x 4 of the expert layers
    assert len(R.compared_leaves(seen.cfg)) == 1 + 12 + 4 + 3 + 16


def test_parameters_after_one_adamw_step_are_the_references(reference):
    check_adamw_step(reference)


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference(
        reference):
    # bf16 products against float32: the losses agree to bf16's rounding
    check_trains_through_to_static(reference, rtol=3e-3)


def test_the_tied_leafs_gradient_is_the_lookups_plus_the_heads(reference):
    """One leaf, read twice: its gradient is the sum of what the look-up
    alone and the head alone would give it (the other use held
    constant)."""
    model, cfg, weights = reference.model()
    assert not any("lm_head" in n for n, _ in model.named_parameters())
    ids = _ids()
    model.loss(model(pt.to_tensor(ids)), pt.to_tensor(ids)).backward()
    got = np.asarray(model.embed_tokens.weight._grad)
    ein = R._ein("float32")
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((2, 1), ids.dtype)], 1)

    def loss(look_up, head):
        total = 0.0
        for row, row_labels in zip(jnp.asarray(ids), labels):
            h = R._hidden(cfg, dict(weights, **{"embed_tokens.weight":
                                                look_up}), row, ein)
            logits = R._logits(cfg, dict(weights, **{"embed_tokens.weight":
                                                     head}), h, ein)
            ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
                logits, row_labels[:, None], -1)[:, 0]
            total = total + jnp.sum(ce[:-1])
        return total / (2 * 23)

    e = weights["embed_tokens.weight"]
    d_look_up, d_head = jax.jit(jax.grad(loss, (0, 1)))(e, e)
    assert float(jnp.abs(d_look_up).max()) > 0 \
        and float(jnp.abs(d_head).max()) > 0
    assert _rel(got, d_look_up + d_head) < 2e-5
    assert _rel(got, d_head) > 1e-2 and _rel(got, d_look_up) > 1e-2


def test_config_reads_the_list_from_first_layer_on_and_the_share(reference):
    c = Lfm2MoeConfig()
    assert (c.num_hidden_layers, c.hidden_size, c.num_dense_layers) \
        == (24, 2048, 2)
    assert c.layer_types.count("conv") == 18
    assert [i for i, k in enumerate(c.layer_types)
            if k == "full_attention"] == [2, 6, 10, 14, 18, 21]
    assert c.num_experts_published == 32
    # the configuration's cut: the published 24 entries, layers 1-5
    cut = Lfm2MoeConfig(num_hidden_layers=5, num_dense_layers=1,
                        first_layer=1, num_experts=8,
                        num_experts_published=32,
                        layer_types=list(c.layer_types))
    assert cut.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv")
    with pytest.raises(ValueError, match="published"):
        Lfm2MoeConfig(num_experts=8, first_expert_held=28,
                      num_experts_published=32)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(num_hidden_layers=5, first_layer=22)
    with pytest.raises(ValueError, match="layer_types"):
        Lfm2MoeConfig(layer_types=["conv", "window"], num_hidden_layers=2)
    with pytest.raises(ValueError, match="source's forms"):
        Lfm2MoeConfig(conv_bias=True)
    model, _, _ = reference.model()
    kinds = [type(getattr(b, "conv", None) or b.self_attn).__name__
             for b in model.layers]
    assert kinds == ["GatedShortConv", "GroupedQueryAttention"] \
        + ["GatedShortConv"] * 3
    assert [type(b.feed_forward).__name__ for b in model.layers] \
        == ["GatedMLP"] + ["RoutedMoE"] * 4
    attention = model.layers[1].self_attn
    assert (attention.head_dim, attention.rope_theta, attention.causal,
            attention.window) == (16, 10000.0, True, None)
    assert attention.q_norm is not None
    moe = model.layers[2].feed_forward
    assert (moe.scoring, moe.top_k, moe.experts_held, moe.activation) \
        == ("sigmoid", 3, range(0, 4), "silu")
    assert moe.shared_experts is None and moe.experts_gate is not None


# -- the gated short convolution as a layer --------------------------------

def test_the_layer_is_the_references_operator():
    layer = nn.GatedShortConv(64, taps=3)
    key = jax.random.key(11)
    for i, (_, p) in enumerate(layer.named_parameters()):
        p.set_value(0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            tuple(p.shape)))
    assert sorted(n for n, _ in layer.named_parameters()) == [
        "conv_weight", "in_proj.weight", "out_proj.weight"]
    w = {k: p.data for k, p in layer.named_parameters()}
    x = np.asarray(jax.random.normal(jax.random.key(12), (2, 40, 64)))
    got = layer(pt.to_tensor(x)).numpy()
    for row in range(2):
        want = R._short_conv({}, w, jnp.asarray(x[row]), _plain)
        np.testing.assert_allclose(got[row], want, rtol=1e-5, atol=1e-6)
    # causal: a later row moves no earlier one, and reaches taps - 1 on
    moved = x.copy()
    moved[0, 10] += 1.0
    delta = np.abs(layer(pt.to_tensor(moved)).numpy() - got).max(-1)
    assert delta[0, :10].max() == 0 and delta[0, 10:13].min() > 0 \
        and delta[0, 13:].max() == 0 and delta[1].max() == 0


# -- attention at the configuration's form, the router, the share -----------

def test_the_attention_layer_is_the_references(reference):
    model, cfg, weights = reference.model()
    layer = model.layers[1].self_attn
    w = R._under(weights, "layers.1.self_attn.")
    x = np.asarray(jax.random.normal(jax.random.key(13), (1, 40, 64)))
    want = R._attention(cfg, w, jnp.asarray(x[0]), _plain)
    for force in (False, True):
        got = layer(pt.to_tensor(x), force_flash=force).numpy()[0]
        np.testing.assert_allclose(got, want, atol=3e-6)


def test_the_router_is_the_published_one_to_its_epsilon():
    """sigmoid scores, top-k, renormalised: the program's weights are the
    reference's to the two epsilons' difference (1e-6 against 1e-20 under
    a sum of k sigmoid scores: under 1e-6 of a weight)."""
    from paddle_tpu.ops import moe as moe_ops
    cfg = dict(num_experts_per_tok=4, routed_scaling_factor=1.0)
    key = jax.random.key(17)
    m = jax.random.normal(key, (48, 64))
    w_r = 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (64, 32))
    chosen, weights = R.route(cfg, m, w_r)
    got_w, got_e = moe_ops.moe_route(
        pt.to_tensor(np.asarray(m)), pt.to_tensor(np.asarray(w_r)),
        pt.to_tensor(np.zeros(32, np.float32)), top_k=4, scale=1.0)
    np.testing.assert_array_equal(got_e.numpy(), chosen)
    gap = np.abs(got_w.numpy() - np.asarray(weights)) / np.asarray(weights)
    assert 0 < gap.max() < 2e-6
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, atol=2e-6)


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The four shares ``range(0, 8) ... range(24, 32)`` of one expert
    layer of 32, top 4 (the configuration's cut): the parts add up to what
    the layer that holds all 32 gives (no shared expert to count once), in
    the program and in the reference."""
    key = jax.random.key(21)
    m = 0.5 * jax.random.normal(key, (2, 24, 64))
    kind = dict(gated=True, scoring="sigmoid")
    whole = nn.RoutedMoE(64, 32, 32, 4, **kind)
    for i, (_, p) in enumerate(whole.named_parameters()):
        p.set_value(0.2 * jax.random.normal(jax.random.fold_in(key, i + 1),
                                            tuple(p.shape)))
    w = {k: p.data for k, p in whole.named_parameters()}

    def layer(first, n):
        return whole if n == 32 else routed_share(
            lambda held: nn.RoutedMoE(64, 32, 32, 4, experts_held=held,
                                      **kind), w, first, n)

    check_expert_shares_add_up(
        R, w, layer, lambda first, n: dict(
            num_experts=n, num_experts_published=32, num_experts_per_tok=4,
            routed_scaling_factor=1.0, first_expert_held=first),
        m, experts=32, held=8)


# -- the configuration file against the catalog's row ------------------------

# the catalog row LFM2-8B-A1B (model-configs guide, architectures.jsonl):
# every number of its ``config``
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1792,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "full_attention",
                    "conv", "conv"]}
REDUCED = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 16384}


def test_the_configuration_files_widths_are_the_catalog_rows():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b.json")) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == set(REDUCED)
    for key, published in CATALOG.items():
        assert cfg[key] == REDUCED.get(key, published), key
    assert (cfg["num_hidden_layers_published"],
            cfg["num_dense_layers_published"], cfg["num_experts_published"],
            cfg["vocab_size_published"]) == (24, 2, 32, 65536)
    assert cfg["first_layer"] == 1 and cfg["first_expert_held"] == 0
    assert [k for k, _ in R.layer_kinds(cfg)] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert [dense for _, dense in R.layer_kinds(cfg)] == [True] + [False] * 4
    # no width is cut: what the model class builds from the file is the
    # published layer
    from benchmark.families.lfm2_moe import _CONFIG_KEYS
    config = Lfm2MoeConfig(**{k: cfg[k] for k in _CONFIG_KEYS})
    assert (config.hidden_size // config.num_attention_heads) == 64
    shapes = R.param_shapes(cfg)
    assert shapes["layers.0.conv.in_proj.weight"] == (2048, 6144)
    assert shapes["layers.0.conv.conv_weight"] == (2048, 3)
    assert shapes["layers.0.feed_forward.down_proj.weight"] == (7168, 2048)
    assert shapes["layers.1.self_attn.k_proj.weight"] == (2048, 512)
    assert shapes["layers.2.feed_forward.experts_up"] == (8, 2048, 1792)
    assert shapes["layers.2.feed_forward.router.weight"] == (2048, 32)
    assert sum(int(np.prod(s)) for s in shapes.values()) \
        == cfg["parameters_held"] == 507_820_160


# -- the experts' ladder is the op's own, as in every other cell -------------

def test_the_expert_layers_pad_on_the_ops_own_ladder(reference, monkeypatch):
    """Nothing of the model or its configuration moves a rung of
    ``F.moe_experts``' ladder of capacities: the cell's even share, 2 x
    8,192 x 4 / 32 = 2,048 rows an expert, IS one of its rungs (an expert
    one row over it runs 4,096: PERF.md section 7 row 44), and a tiny
    model's expert layers count ``rows_computed`` on ``MIN_ROWS`` x 2^n."""
    from paddle_tpu.ops import moe as moe_ops
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_8b_a1b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "traffic",
                           "causal_pretrain_2x8k.json")) as f:
        traffic = json.load(f)
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    share = tokens * cfg["num_experts_per_tok"] // cfg["num_experts_published"]
    assert (tokens, share) == (16384, 2048)
    assert share in moe_ops._ladder(tokens, moe_ops.MIN_ROWS)

    monkeypatch.setattr(moe_ops, "MIN_ROWS", 4)
    model, _, _ = reference.model()
    ids = _ids(rows=2, seq=24)
    model(pt.to_tensor(ids))
    rungs = moe_ops._ladder(ids.size, 4)
    assert rungs == (4, 8, 16, 32, 48)
    for block in model.layers[1:]:
        slots, dropped, fullest, calls, computed = (
            int(v) for v in np.asarray(block.feed_forward.stats.data))
        assert (dropped, calls) == (0, 1) and 0 < slots <= 4 * fullest
        # four held experts, each on the rung that holds its rows: the
        # fullest one's rung at most, the first rung at least
        top = next(r for r in rungs if r >= fullest)
        assert slots <= computed <= 4 * top and computed % 4 == 0
        assert computed >= max(top, 4 * 4)
