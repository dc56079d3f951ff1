"""Phi-4-mini-flash on the training path, at a tiny size on the CPU, against
the benchmark's plain float32 reference (benchmark/reference/phi4_flash.py,
which imports nothing of paddle_tpu): a decoder-hybrid-decoder — Mamba-1
mixers and windowed differential attention, one full attention layer that
hands on its keys and values, a Mamba layer that hands on its scan memory,
gated memory units and cross attention that read them — with layer norms,
no positions, a tied head, and the model trained through ``jit.to_static``
+ ``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``. The contract with
the reference is tests/family_contract.py's; the scan op's two routes are
in tests/test_ssm_ops.py.
"""
import json
import math
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
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.models.phi4_flash import (                      # noqa: E402
    Phi4FlashConfig, Phi4FlashForCausalLM, published_plan)
from benchmark.reference import phi4_flash as R                 # noqa: E402
from family_contract import (Family, Reference,                 # noqa: E402
                             check_matches_reference,
                             check_trains_through_to_static,
                             plain as _plain)

# biases drawn, not zero as published: a bias that is zero is not compared
FAMILY = Family(R, Phi4FlashForCausalLM, Phi4FlashConfig.tiny,
                cfg_extra=dict(bias_std=0.02))
# a stack in which each hand-over has TWO readers: the memory's and the keys'
# and values' gradients are sums over them
TWO_READERS = dict(
    layer_plan=["mamba", "window_attention", "mamba", "full_attention",
                "memory_unit", "cross_attention", "memory_unit",
                "cross_attention"],
    first_layer=0, num_hidden_layers=8, num_hidden_layers_published=8)
# gradients that all but cancel, so that both sides read rounding beside
# them: a key bias adds one number to a whole soft-max row (zero in exact
# arithmetic); lambda's is one scalar summed over every row and pair of a
# layer behind a norm that removes the result's scale (1e-7 to 1e-3 here,
# the two sides 2e-9 apart)
CANCELLING = {"k_proj.bias": 1e-8, ".lambda_": 1e-8}


@pytest.fixture(scope="module")
def reference():
    return Reference(FAMILY)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute,plan", [
    (True, {}), (True, TWO_READERS)], ids=["the-cut", "two-readers"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        reference, recompute, plan):
    """The configuration's cut (source layers 14-19: Mamba, window
    attention, the Mamba that gives the memory, the full attention that
    gives K and V, a memory unit, a cross attention), and eight layers in
    which both hand-overs have two readers, so that the memory's, K's and
    V's gradients are sums over them; every block recomputed, so the
    handed tensors cross checkpoint boundaries; two sequences."""
    seen = check_matches_reference(reference, recompute,
                                   grad_atol=CANCELLING, **plan)
    kinds = [kind for kind, _, _ in R.layer_kinds(seen.cfg)]
    if plan:
        assert kinds == plan["layer_plan"]
        gives = [i for i, (_, _, g) in enumerate(R.layer_kinds(seen.cfg))
                 if g]
        assert gives == [2, 3]      # the LAST mamba in front of a reader
    else:
        assert kinds == ["mamba", "window_attention", "mamba",
                         "full_attention", "memory_unit", "cross_attention"]
        assert [s for _, s, _ in R.layer_kinds(seen.cfg)] \
            == [14, 15, 16, 17, 18, 19]
    assert tuple(seen.outputs[0].shape) == (2, 24, 256)
    assert not any("lm_head" in n or "position" in n
                   for n, _ in seen.model.named_parameters())
    # what `correct` compares norm by norm: every matrix (A_log and the
    # taps among them) but the Mamba mixers' x_proj, whose gradient's norm
    # is a few sums that all but cancel (compared_leaves' docstring)
    matrices = [n for n, p in seen.model.named_parameters()
                if len(p.shape) >= 2]
    compared = R.compared_leaves(seen.cfg)
    assert sorted(set(matrices) - set(compared)) == [
        f"layers.{i}.mixer.x_proj.weight" for i in (0, 2)]
    assert set(compared) < set(matrices) and len(compared) == (
        41 if not plan else 51)


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference(
        reference):
    # bf16 products against float32: the losses agree to bf16's rounding
    check_trains_through_to_static(reference, rtol=3e-3)


# -- the configuration ------------------------------------------------------

def test_config_reads_the_plan_by_source_index():
    plan = published_plan()
    assert [plan.count(k) for k in (
        "mamba", "window_attention", "full_attention", "memory_unit",
        "cross_attention")] == [9, 8, 1, 7, 7]
    assert list(plan) == R.published_plan()
    assert plan[16] == "mamba" and plan[17] == "full_attention" \
        and plan[15] == "window_attention" and plan[18] == "memory_unit"
    c = Phi4FlashConfig()
    assert (c.num_hidden_layers, c.memory_layer, c.kv_layer, c.head_dim,
            c.mamba_d_inner) == (32, 16, 17, 64, 5120)
    cut = Phi4FlashConfig(num_hidden_layers=6, first_layer=14,
                          num_hidden_layers_published=32, vocab_size=25008)
    assert [cut.kind(i) for i in range(6)] == list(plan[14:20])
    with pytest.raises(ValueError, match="not among the held"):
        Phi4FlashConfig(num_hidden_layers=4, first_layer=17,
                        num_hidden_layers_published=32)
    with pytest.raises(ValueError, match="layer_plan"):
        Phi4FlashConfig(num_hidden_layers=8, first_layer=30,
                        num_hidden_layers_published=32)
    with pytest.raises(ValueError, match="no mamba in front"):
        Phi4FlashConfig(layer_plan=["memory_unit", "mamba"],
                        num_hidden_layers=2)


def test_lambda_init_follows_the_source_index_and_the_window_the_plan(
        reference):
    model, _, _ = reference.model()
    mixers = [b.mixer for b in model.layers]
    assert [type(m).__name__ for m in mixers] == [
        "MambaMixer", "DifferentialAttention", "MambaMixer",
        "DifferentialAttention", "GatedMemoryUnit", "DifferentialAttention"]
    for i in (1, 3, 5):
        assert mixers[i].lambda_init == pytest.approx(
            0.8 - 0.6 * math.exp(-0.3 * (14 + i)))
    assert [mixers[i].window for i in (1, 3, 5)] == [8, None, None]
    assert [mixers[i].cross for i in (1, 3, 5)] == [False, False, True]
    assert not hasattr(mixers[5], "k_proj")
    assert [b.gives for b in model.layers] == [False, False, True, True,
                                               False, False]


# -- differential attention as a layer ---------------------------------------

def _attention(window=None, cross=False, depth=5, seed=31):
    layer = nn.DifferentialAttention(64, 8, 4, 8, depth=depth, window=window,
                                     cross=cross)
    key = jax.random.key(seed)
    for i, (name, p) in enumerate(sorted(layer.named_parameters())):
        p.set_value((0.3 if "lambda" in name else 0.2)
                    * jax.random.normal(jax.random.fold_in(key, i),
                                        tuple(p.shape))
                    + (1.0 if name == "subln.weight" else 0.0))
    return layer, {k: p.data for k, p in layer.named_parameters()}


def _two_maps(w, x, k, v, depth, window):
    """The layer by its two materialised soft-max maps, pair by pair."""
    t, hd, heads, kv = x.shape[0], 8, 8, 4
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
    lam = float(jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
                - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"]))) + lam_init
    q = (x @ w["q_proj.weight"] + w["q_proj.bias"]).reshape(t, heads, hd)
    at = np.arange(t)
    seen = at[:, None] >= at[None, :]
    if window:
        seen &= at[:, None] - at[None, :] < window
    out = []
    for j in range(heads // 2):
        g = j // (heads // kv)
        maps = [jax.nn.softmax(jnp.where(
            seen, q[:, 2 * j + i] @ k[2 * g + i].T / math.sqrt(hd), -jnp.inf),
            -1) for i in (0, 1)]
        o = (maps[0] - lam * maps[1]) @ v[g]
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
        out.append((1 - lam_init) * o * w["subln.weight"])
    return jnp.concatenate(out, -1) @ w["o_proj.weight"] + w["o_proj.bias"]


@pytest.mark.parametrize("window,cross", [(None, False), (6, False),
                                          (None, True)],
                         ids=["full", "window", "keys-from-the-caller"])
def test_differential_attention_is_its_two_materialised_maps(window, cross):
    layer, w = _attention(window, cross)
    giver, gw = _attention(seed=32)
    x = np.asarray(jax.random.normal(jax.random.key(33), (2, 20, 64)))
    other = np.asarray(jax.random.normal(jax.random.key(34), (2, 20, 64)))
    for force in (False, True):
        if cross:
            kv = giver.key_value(pt.to_tensor(other))
            got = layer(pt.to_tensor(x), kv=kv, force_flash=force).numpy()
        else:
            got, k_out, v_out = layer(pt.to_tensor(x), return_kv=True,
                                      force_flash=force)
            got = got.numpy()
            assert tuple(k_out.shape) == (2, 4, 20, 8) \
                and tuple(v_out.shape) == (2, 2, 20, 16)
        for row in range(2):
            src, sw = (other[row], gw) if cross else (x[row], w)
            k = (src @ sw["k_proj.weight"] + sw["k_proj.bias"]).reshape(
                20, 4, 8).transpose(1, 0, 2)
            v = (src @ sw["v_proj.weight"] + sw["v_proj.bias"]).reshape(
                20, 2, 16).transpose(1, 0, 2)
            want = _two_maps(w, jnp.asarray(x[row]), k, v, 5, window)
            np.testing.assert_allclose(got[row], want, atol=5e-6)
            # and the reference's own layer function
            ref = R.differential_attention(
                dict(hidden_size=64, num_attention_heads=8,
                     num_key_value_heads=4, layer_norm_eps=1e-5), w,
                jnp.asarray(x[row]), jnp.asarray(k), jnp.asarray(v), 5,
                window, _plain)
            np.testing.assert_allclose(got[row], ref, atol=5e-6)
    with pytest.raises(ValueError, match="cross layer takes"):
        layer(pt.to_tensor(x)) if cross else layer(
            pt.to_tensor(x), kv=giver.key_value(pt.to_tensor(x)))


def test_a_dropped_lambda_and_an_ignored_window_are_seen():
    """What the tolerances above are for: plain attention (lambda = 0) and
    a windowed layer that reads the whole triangle are 1,000 x outside."""
    layer, w = _attention(window=6)
    x = np.asarray(jax.random.normal(jax.random.key(35), (1, 20, 64)))
    got = layer(pt.to_tensor(x)).numpy()[0]
    k = (x[0] @ w["k_proj.weight"] + w["k_proj.bias"]).reshape(
        20, 4, 8).transpose(1, 0, 2)
    v = (x[0] @ w["v_proj.weight"] + w["v_proj.bias"]).reshape(
        20, 2, 16).transpose(1, 0, 2)
    unwindowed = _two_maps(w, jnp.asarray(x[0]), k, v, 5, None)
    assert np.abs(got - np.asarray(unwindowed)).max() > 5e-3
    ctx = jax.random.normal(jax.random.key(36), (1, 8, 20, 16))
    args = [pt.to_tensor(np.asarray(t)) for t in (
        ctx, w["lambda_q1"], w["lambda_k1"], w["lambda_q2"], w["lambda_k2"],
        w["subln.weight"])]
    with_lambda = F.differential_heads(*args, 0.5).numpy()
    plain = np.asarray(ctx[:, 0::2] / jnp.sqrt(jnp.mean(
        ctx[:, 0::2] ** 2, -1, keepdims=True) + 1e-5) * 0.5
        * w["subln.weight"])
    plain = np.moveaxis(plain, 1, 2).reshape(1, 20, 64)
    assert np.abs(with_lambda - plain).max() > 5e-3


def test_the_pair_norm_has_one_scale_for_all_pairs():
    layer, _ = _attention()
    assert tuple(layer.subln.weight.shape) == (16,)
    ctx = np.asarray(jax.random.normal(jax.random.key(37), (1, 8, 6, 16)))
    args = [pt.to_tensor(ctx)] + [getattr(layer, n) for n in (
        "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")]
    one = F.differential_heads(*args, layer.subln.weight, 0.4).numpy()
    layer.subln.weight.set_value(2.0 * layer.subln.weight.data)
    two = F.differential_heads(*args, layer.subln.weight, 0.4).numpy()
    np.testing.assert_allclose(two, 2.0 * one, rtol=1e-6)   # every pair
    # float32 inside whatever ctx is
    half = F.differential_heads(pt.to_tensor(ctx).astype("bfloat16"),
                                *args[1:], layer.subln.weight, 0.4)
    assert half.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="even number of heads"):
        F.differential_heads(pt.to_tensor(ctx[:, :3]), *args[1:],
                             layer.subln.weight, 0.4)


# -- the Mamba mixer and the memory unit as layers ---------------------------

def test_the_mamba_mixer_is_the_references_and_hands_on_the_ungated_scan(
        reference):
    model, cfg, weights = reference.model()
    layer = model.layers[2].mixer
    w = R._under(weights, "layers.2.mixer.")
    x = np.asarray(jax.random.normal(jax.random.key(41), (2, 24, 64)))
    out, memory = layer(pt.to_tensor(x), return_memory=True)
    assert tuple(memory.shape) == (2, 24, 128)
    for row in range(2):
        want, want_memory = R._mamba(cfg, w, jnp.asarray(x[row]), _plain)
        np.testing.assert_allclose(out.numpy()[row], want, atol=3e-6)
        np.testing.assert_allclose(memory.numpy()[row], want_memory,
                                   atol=3e-6)
    # before the gate: the gated value is another tensor
    z = (x @ np.asarray(w["in_proj.weight"]))[..., 128:]
    assert np.abs(memory.numpy() * np.asarray(jax.nn.silu(z))
                  - memory.numpy()).max() > 1e-3
    unit = model.layers[4].mixer
    got = unit(pt.to_tensor(x), memory).numpy()
    uw = R._under(weights, "layers.4.mixer.")
    for row in range(2):
        np.testing.assert_allclose(
            got[row], R._memory_unit(uw, jnp.asarray(x[row]),
                                     memory.numpy()[row], _plain), atol=3e-6)


# -- the configuration file against the catalog's row ------------------------

# the catalog row Phi-4-mini-flash-reasoning (model-configs guide,
# architectures.jsonl): every number of its ``config``
CATALOG = {
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064}
REDUCED = {"num_hidden_layers": 6, "vocab_size": 25008}


def test_the_configuration_files_widths_are_the_catalog_rows():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "phi4_mini_flash.json")) as f:
        cfg = json.load(f)
    assert set(cfg["reduced"]) == set(REDUCED)
    for key, published in CATALOG.items():
        assert cfg[key] == REDUCED.get(key, published), key
    assert (cfg["num_hidden_layers_published"], cfg["vocab_size_published"],
            cfg["first_layer"]) == (32, 200064, 14)
    assert cfg["vocab_size"] * 8 == cfg["vocab_size_published"]
    assert cfg["layer_plan"] == list(published_plan())
    assert [k for k, _, _ in R.layer_kinds(cfg)] == [
        "mamba", "window_attention", "mamba", "full_attention",
        "memory_unit", "cross_attention"]
    # no width is cut: what the model class builds from the file is the
    # published layer
    from benchmark.families.phi4_flash import _CONFIG_KEYS
    c = Phi4FlashConfig(**{k: cfg[k] for k in _CONFIG_KEYS})
    assert (c.hidden_size, c.intermediate_size, c.num_attention_heads,
            c.num_key_value_heads, c.head_dim, c.sliding_window,
            c.mamba_d_inner, c.mamba_d_state, c.mamba_d_conv,
            c.mamba_dt_rank) == (2560, 10240, 40, 20, 64, 512, 5120, 16, 4,
                                 160)
    assert (c.memory_layer, c.kv_layer) == (16, 17)
    from benchmark import phi4_flash_costs as costs
    assert costs.total_params(cfg) == cfg["parameters_held"] == 697_094_272
    assert costs.total_params(costs.published(cfg)) \
        == cfg["parameters_published"] == 3_852_562_944
    assert sum(int(np.prod(s)) for s in R.param_shapes(cfg).values()) \
        == 697_094_272
