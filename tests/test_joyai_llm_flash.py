"""JoyAI-LLM-Flash on the training path, at a tiny size on the CPU, against
the benchmark's plain float32 reference (benchmark/reference/
joyai_llm_flash.py, which imports nothing of paddle_tpu): the rotary
embedding, multi-head latent attention through the flash kernels at two
head sizes, the gated routed experts and the chip's share of them, the
multi-token-prediction module, and the model trained through
``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``.
The contract with the reference is tests/family_contract.py's.
"""
import functools
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
from paddle_tpu.models.joyai_llm_flash import (                 # noqa: E402
    JoyAIFlashConfig, JoyAIFlashForCausalLM, MultiTokenPredictor)
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.ops import moe as moe_ops                       # noqa: E402
from paddle_tpu.ops.pallas import flash_attention_mod as flash_mod  # noqa: E402,E501
from benchmark.reference import joyai_llm_flash as R            # noqa: E402
from family_contract import (Family, Reference,                 # noqa: E402
                             check_expert_shares_add_up,
                             check_matches_reference,
                             check_trains_through_to_static, ids,
                             plain as _plain, rel as _rel)

_ids = functools.partial(ids, seq=21)


# two logits, the main model's and the MTP module's, and a loss of both
FAMILY = Family(
    R, JoyAIFlashForCausalLM, JoyAIFlashConfig.tiny,
    batch=lambda seed: (_ids(seed=seed),),
    loss=lambda model, outputs, batch: model.loss(outputs[0], outputs[1],
                                                  batch[0]),
    outputs=("logits", "mtp_logits"))


@pytest.fixture(scope="module")
def reference():
    return Reference(FAMILY)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_both_logits_losses_and_every_gradient(
        reference, recompute):
    seen = check_matches_reference(reference, recompute)
    model, cfg, weights = seen.model, seen.cfg, seen.weights
    batch = tuple(jnp.asarray(a) for a in seen.batch)
    logits, _ = seen.outputs

    main, mtp = reference.once(
        "loss_terms", lambda: R.loss_terms(cfg, weights, batch))
    only_main = model.loss(logits, None, pt.to_tensor(seen.batch[0]))
    assert abs(float(only_main.numpy()) - float(main)) < 1e-5
    assert abs(float(seen.loss.numpy()) - float(main + 0.3 * mtp)) < 1e-5
    # the module's term by itself: what the two-term loss adds, over 0.3
    assert abs((float(seen.loss.numpy()) - float(only_main.numpy())) / 0.3
               - float(mtp)) < 1e-4

    assert len(R.compared_leaves(cfg)) >= 40
    # embedding and head are the main model's: the module's pass reaches
    # them, so their gradient differs from the main term's alone
    main_grad = reference.once("main_grad", lambda: jax.jit(jax.grad(
        lambda q: R.loss_terms(cfg, q, batch)[0]))(weights))
    for name in ("embed_tokens.weight", "lm_head.weight"):
        assert _rel(main_grad[name], seen.want_grad[name]) > 1e-3, name


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference(
        reference):
    # bf16 products against float32: the losses agree to bf16's rounding
    got, _ = check_trains_through_to_static(reference, rtol=2e-3)
    assert got[2] < got[0]


def test_the_module_cannot_see_the_id_it_is_fed_last(reference):
    """The last position is fed ``id_0`` for ``id_S``; attention is causal,
    so the module's logits before it do not move with that id."""
    model, _, _ = reference.model()
    a = _ids()
    b = a.copy()
    b[:, 0] = (b[:, 0] + 7) % 256          # moves every main logit ...
    la, ma = model(pt.to_tensor(a))
    lb, mb = model(pt.to_tensor(b))
    assert np.abs(la.numpy() - lb.numpy()).max() > 1e-3
    # ... and the module's too; so compare two runs that differ only in
    # what the roll carries to the end: ids equal from position 1 on
    c = a.copy()
    c[:, -1] = (c[:, -1] + 3) % 256        # the last id: fed to S - 2 only
    _, mc = model(pt.to_tensor(c))
    np.testing.assert_array_equal(ma.numpy()[:, :-2], mc.numpy()[:, :-2])


def test_config_checks_layers_held_range_and_pattern():
    c = JoyAIFlashConfig()
    assert c.pattern == "D" + "E" * 39 and c.n_routed_experts_published == 256
    assert JoyAIFlashConfig.tiny().pattern == "DEE"
    assert JoyAIFlashConfig.tiny(first_k_dense_replace=0,
                                 moe_layer_freq=2).pattern == "EDE"
    with pytest.raises(ValueError, match="first_k_dense_replace"):
        JoyAIFlashConfig.tiny(first_k_dense_replace=4)
    with pytest.raises(ValueError, match="published"):
        JoyAIFlashConfig.tiny(first_expert_held=14)
    with pytest.raises(ValueError, match="num_nextn_predict_layers"):
        JoyAIFlashConfig.tiny(num_nextn_predict_layers=2)
    with pytest.raises(ValueError, match="rope_interleave"):
        JoyAIFlashConfig.tiny(rope_interleave=False)
    model = JoyAIFlashForCausalLM(JoyAIFlashConfig.tiny(
        num_nextn_predict_layers=0))
    assert not any(isinstance(b, MultiTokenPredictor) for b in model.layers)
    logits, none = model(pt.to_tensor(_ids()))
    assert none is None and tuple(logits.shape) == (2, 21, 256)
    names = dict(JoyAIFlashForCausalLM(JoyAIFlashConfig.tiny())
                 .named_parameters())
    for name in ("layers.0.self_attn.kv_a_proj_with_mqa.weight",
                 "layers.0.mlp.gate_proj.weight", "layers.1.mlp.experts_gate",
                 "layers.1.mlp.shared_experts.up_proj.weight",
                 "layers.3.eh_proj.weight", "layers.3.enorm.weight",
                 "layers.3.hnorm.weight", "layers.3.shared_head.norm.weight"):
        assert name in names, name


# -- the rotary embedding ---------------------------------------------------

def _written_rotary(x, theta, positions=None):
    """The issue's formula, a pair at a time, in float64."""
    x = np.asarray(x, np.float64)
    s, d = x.shape[-2], x.shape[-1]
    pos = np.arange(s) if positions is None else np.asarray(positions)
    out = np.zeros_like(x)
    for j in range(d // 2):
        angle = pos * theta ** (-2.0 * j / d)
        cos, sin = np.cos(angle)[:, None], np.sin(angle)[:, None]
        even, odd = x[..., 2 * j:2 * j + 1], x[..., 2 * j + 1:2 * j + 2]
        out[..., j:j + 1] = even * cos - odd * sin
        out[..., j + d // 2:j + d // 2 + 1] = odd * cos + even * sin
    return out


@pytest.mark.parametrize("theta", [10000.0, 32000000.0])
def test_rotary_embedding_is_the_written_formula_and_keeps_norms(theta):
    x = np.asarray(jax.random.normal(jax.random.key(1), (2, 3, 37, 64)))
    got = F.rotary_embedding(pt.to_tensor(x), theta=theta).numpy()
    np.testing.assert_allclose(got, _written_rotary(x, theta), atol=2e-5)
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    # positions given: a window that starts at 5; and the reference's own
    at = np.arange(5, 42)
    moved = F.rotary_embedding(pt.to_tensor(x), pt.to_tensor(at),
                               theta=theta).numpy()
    np.testing.assert_allclose(moved, _written_rotary(x, theta, at),
                               atol=2e-5)
    np.testing.assert_allclose(
        F.rotary_embedding(pt.to_tensor(x[0, 0]), theta=theta).numpy(),
        R.rotary(jnp.asarray(x[0, 0]), theta), atol=1e-6)
    # the other pairing keeps its layout: pairs (j, j + D/2)
    halves = F.rotary_embedding(pt.to_tensor(x), theta=theta,
                                interleaved=False).numpy()
    shuffled = np.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    np.testing.assert_allclose(
        F.rotary_embedding(pt.to_tensor(shuffled), theta=theta,
                           interleaved=False).numpy(), got, atol=1e-6)
    assert np.abs(halves - got).max() > 1e-2
    with pytest.raises(ValueError, match="even"):
        F.rotary_embedding(pt.to_tensor(x[..., :63]))


def test_rotary_scores_depend_on_the_distance_between_positions_only():
    q = np.asarray(jax.random.normal(jax.random.key(2), (1, 64)))
    k = np.asarray(jax.random.normal(jax.random.key(3), (1, 64)))

    def score(pq, pk):
        rq = F.rotary_embedding(pt.to_tensor(q), pt.to_tensor(
            np.asarray([pq])), theta=10000.0).numpy()
        rk = F.rotary_embedding(pt.to_tensor(k), pt.to_tensor(
            np.asarray([pk])), theta=10000.0).numpy()
        return float((rq * rk).sum())

    assert abs(score(9, 4) - score(105, 100)) < 1e-4
    assert abs(score(9, 4) - score(1005, 1000)) < 2e-3
    assert abs(score(9, 4) - score(9, 5)) > 1e-3
    assert abs(score(0, 0) - float((q * k).sum())) < 1e-5


def test_rotary_gradient_by_finite_difference_and_float32_under_bf16():
    x = np.asarray(jax.random.normal(jax.random.key(4), (5, 8)), np.float32)
    w = np.asarray(jax.random.normal(jax.random.key(5), (5, 8)), np.float32)
    t = pt.to_tensor(x)
    t.stop_gradient = False
    (F.rotary_embedding(t, theta=100.0) * pt.to_tensor(w)).sum().backward()
    got = np.asarray(t._grad)

    def f(v):
        return float((_written_rotary(v, 100.0) * w).sum())

    for i, j in ((0, 0), (2, 3), (4, 7), (3, 4)):
        bump = np.zeros_like(x, np.float64)
        bump[i, j] = 1e-4
        numeric = (f(x + bump) - f(x - bump)) / 2e-4
        assert abs(got[i, j] - numeric) < 1e-3, (i, j)
    # bfloat16 in, bfloat16 out, the rotation in float32 between
    half = F.rotary_embedding(pt.to_tensor(x).astype("bfloat16"),
                              theta=100.0)
    assert str(half.dtype).endswith("bfloat16")
    np.testing.assert_allclose(
        half.astype("float32").numpy(),
        _written_rotary(np.asarray(jnp.asarray(x, jnp.bfloat16),
                                   np.float32), 100.0), atol=2e-2)


# -- latent attention through the flash kernels ------------------------------

def _qkv(seq, d, dv, heads=2, seed=6):
    keys = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(keys[0], (1, heads, seq, d)),
            jax.random.normal(keys[1], (1, heads, seq, d)),
            jax.random.normal(keys[2], (1, heads, seq, dv)),
            jax.random.normal(keys[3], (1, heads, seq, dv)))


def _flash_value_and_grads(q, k, v, cot, block_q=512, block_k=1024):
    def f(q, k, v):
        out = flash_mod._flash(q, k, v, None, None, jnp.zeros((2,), jnp.int32),
                               True, None, block_q, block_k, 0.0)
        return jnp.sum(out * cot), out
    (_, out), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


@pytest.mark.parametrize("blocks", [(512, 1024), (16, 32)],
                         ids=["one_block", "several_blocks"])
def test_flash_kernels_at_head_sizes_192_and_128_causal_against_sdpa(blocks):
    q, k, v, cot = _qkv(40, 192, 128)
    got = _flash_value_and_grads(q, k, v, cot, *blocks)

    def plain(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(192.0)
        s = jnp.where(jnp.tril(jnp.ones((40, 40), bool)), s, -jnp.inf)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        return jnp.sum(out * cot), out
    (_, out), grads = jax.value_and_grad(plain, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    assert got[0].shape == (1, 2, 40, 128) and got[3].shape == v.shape
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, (out,) + grads):
        assert _rel(a, b) < 2e-5, name


def test_flash_kernels_at_two_head_sizes_equal_todays_on_a_padded_v():
    """What the kernels as they were could do for v narrower than q: pad v
    (and dO) with zero columns to q's head size. The two-size kernels give
    the same bits without the padding."""
    q, k, v, cot = _qkv(40, 192, 128, seed=7)
    got = _flash_value_and_grads(q, k, v, cot, 16, 32)
    pad = [(0, 0)] * 3 + [(0, 64)]
    padded = _flash_value_and_grads(q, k, jnp.pad(v, pad), jnp.pad(cot, pad),
                                    16, 32)
    np.testing.assert_array_equal(got[0], padded[0][..., :128])
    np.testing.assert_array_equal(got[1], padded[1])
    np.testing.assert_array_equal(got[2], padded[2])
    np.testing.assert_array_equal(got[3], padded[3][..., :128])
    assert not np.asarray(padded[0][..., 128:]).any()


def test_flash_at_one_head_size_holds_two_kernels_of_that_size():
    """With ``dv == d`` every 4-D array of the program is ``d`` wide, as
    before the kernels took two sizes (the compiled seq-512 text is held
    in tests/test_chip_compile.py)."""
    import re
    q, k, v, cot = (jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, 2, 40, 64),) * 4)
    text = str(jax.make_jaxpr(_flash_value_and_grads)(q, k, v, cot))
    assert text.count("pallas_call") == 2
    for name in ("flash_fwd", "flash_bwd"):
        assert name in text
    assert set(re.findall(r"f32\[\d+,\d+,\d+,(\d+)\]", text)) == {"64"}
    assert set(re.findall(r"f32\[2,40,(\d+)\]", text)) == {"64"}


def test_mla_through_the_flash_kernels_against_sdpa_and_the_reference():
    layer = nn.MultiHeadLatentAttention(64, 4, 48, 32, 128, 64, 128,
                                        rope_theta=32000000.0)
    x = pt.to_tensor(np.asarray(
        jax.random.normal(jax.random.key(8), (1, 40, 64))))
    before = monitor.snapshot("flash_attention")
    got = layer(x, force_flash=True)            # the kernels, interpreted
    q, k, v = layer.qkv(x)
    assert tuple(q.shape) == tuple(k.shape) == (1, 4, 40, 192)
    assert tuple(v.shape) == (1, 4, 40, 128)
    plain = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    plain = layer.o_proj(plain.transpose([0, 2, 1, 3]).reshape([1, 40, 512]))
    np.testing.assert_allclose(got.numpy(), plain.numpy(), atol=2e-5)
    # off a TPU, and unforced, the dispatch takes that same path
    np.testing.assert_allclose(layer(x).numpy(), plain.numpy(), atol=1e-6)
    after = monitor.snapshot("flash_attention")
    count = lambda snap, key: snap.get("flash_attention." + key, 0)
    assert count(after, "kernel_traced") - count(before, "kernel_traced") == 1
    assert count(after, "xla_traced") - count(before, "xla_traced") == 1
    # and both are the reference's attention
    w = {name: p.data for name, p in layer.named_parameters()}
    cfg = dict(num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=128,
               rms_norm_eps=1e-6, rope_theta=32000000.0)
    want = R._mla(cfg, w, x.data[0], _plain)
    np.testing.assert_allclose(got.numpy()[0], want, atol=2e-5)
    # one rotary key head serves every head
    np.testing.assert_array_equal(k.numpy()[0, 0, :, 128:],
                                  k.numpy()[0, 3, :, 128:])


def test_the_blocks_shrink_only_where_vmem_asks():
    fit = flash_mod._blocks_that_fit
    assert fit(512, 64, 64, 2, 512, 1024) == (512, 1024)       # BERT's
    assert fit(4096, 128, 128, 2, 512, 1024) == (512, 1024)
    assert fit(8192, 128, 128, 2, 512, 1024) == (512, 512)     # nemotron's
    assert fit(4096, 192, 128, 2, 512, 1024) == (512, 1024)
    # this model's: 512 x 512 since PR 32, beside a whole side held in ONE
    # VMEM buffer (two of them left room for 256 x 256)
    assert fit(8192, 192, 128, 2, 512, 1024) == (512, 512)
    single = flash_mod._single_buffered
    assert single(8192, 192, 128, 2) and not single(8192, 128, 128, 2)
    assert not single(4096, 192, 128, 2) and not single(512, 64, 64, 2)


# -- gated experts ----------------------------------------------------------

def _gated_weights(cfg, seed=2):
    """A gated expert layer's weights with ALL the published experts."""
    d, e = cfg["hidden_size"], cfg["n_routed_experts_published"]
    f = cfg["moe_intermediate_size"]
    k = jax.random.split(jax.random.key(seed), 7)
    return {"router.weight": jax.random.normal(k[0], (d, e)),
            "experts_gate": 0.3 * jax.random.normal(k[1], (e, d, f)),
            "experts_up": 0.3 * jax.random.normal(k[2], (e, d, f)),
            "experts_down": 0.3 * jax.random.normal(k[3], (e, f, d)),
            "shared_experts.gate_proj.weight":
                0.3 * jax.random.normal(k[4], (d, f)),
            "shared_experts.up_proj.weight":
                0.3 * jax.random.normal(k[5], (d, f)),
            "shared_experts.down_proj.weight":
                0.3 * jax.random.normal(k[6], (f, d))}


def _gated_layer(cfg, weights, first, held):
    layer = nn.RoutedMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["n_routed_experts_published"], cfg["num_experts_per_tok"],
        d_shared=cfg["moe_intermediate_size"],
        experts_held=range(first, first + held),
        routed_scaling_factor=cfg["routed_scaling_factor"], gated=True)
    layer.router.weight.set_value(weights["router.weight"])
    for name in ("experts_gate", "experts_up", "experts_down"):
        getattr(layer, name).set_value(weights[name][first:first + held])
    for name in ("gate_proj", "up_proj", "down_proj"):
        getattr(layer.shared_experts, name).weight.set_value(
            weights[f"shared_experts.{name}.weight"])
    return layer


def test_the_shares_of_a_gated_expert_layer_add_up_to_the_uncut_layer():
    """Guide ``model-configs`` section 4: the parts all 4 shares give (4 of
    16 experts each), the shared expert counted once, are the whole
    layer."""
    cfg = dict(vars(JoyAIFlashConfig.tiny()))
    weights = _gated_weights(cfg)
    u = jax.random.normal(jax.random.key(7), (2, 13, cfg["hidden_size"]))
    # each share against the reference given the same share, and their sum
    routed = check_expert_shares_add_up(
        R, weights, lambda first, n: _gated_layer(cfg, weights, first, n),
        lambda first, n: dict(cfg, n_routed_experts=n,
                              first_expert_held=first),
        u, experts=16, held=4, part_atol=2e-4, sum_atol=5e-4,
        shared=lambda layer, t: layer.shared_experts(t))
    assert routed == 2 * 13 * cfg["num_experts_per_tok"]   # every slot, once


def _per_token(x, experts, weights, gate, up, down, first):
    """``F.moe_experts``' gated form one token and one choice at a time."""
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for e, w in zip(experts[t], weights[t]):
            j = int(e) - first
            if 0 <= j < up.shape[0]:
                a, u = x[t] @ gate[j], x[t] @ up[j]
                out[t] += w * ((a / (1 + np.exp(-a))) * u) @ down[j]
    return out


@pytest.mark.parametrize("min_rows", [512, 4])
@pytest.mark.parametrize("published,fullest_at_least", [(64, 1), (8, 12)],
                         ids=["thin", "crowded"])
def test_gated_experts_give_the_per_token_loop_forward_and_gradient(
        monkeypatch, min_rows, published, fullest_at_least):
    monkeypatch.setattr(moe_ops, "MIN_ROWS", min_rows)
    tokens, d, f, held, k = 33, 16, 12, 4, 3
    keys = jax.random.split(jax.random.key(published), 6)
    x = jax.random.normal(keys[0], (tokens, d))
    gate, up = (0.4 * jax.random.normal(keys[i], (held, d, f))
                for i in (1, 2))
    down = 0.4 * jax.random.normal(keys[3], (held, f, d))
    weights, experts = F.moe_route(
        pt.to_tensor(np.asarray(x)),
        pt.to_tensor(np.asarray(jax.random.normal(keys[4], (d, published)))),
        top_k=k, scale=2.5)
    first = 2

    def run(x, weights, gate, up, down):
        y, stats = moe_ops._routed(x, experts.data, weights, up, down, gate,
                                   first=first, dot_dtype=jnp.float32)
        return jnp.sum(y * y), (y, stats)

    (_, (y, stats)), grads = jax.value_and_grad(
        run, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            x, weights.data, gate, up, down)
    assert int(stats[2]) >= fullest_at_least and int(stats[1]) == 0
    want = _per_token(*(np.asarray(a, np.float64) for a in (
        x, experts.data, weights.data, gate, up, down)), first)
    np.testing.assert_allclose(y, want, atol=1e-4)

    def plain(x, weights, gate, up, down):
        y = jnp.zeros_like(x)
        for j in range(held):
            g = jnp.sum(jnp.where(experts.data == first + j, weights, 0.0),
                        -1)
            y = y + g[:, None] * ((jax.nn.silu(x @ gate[j]) * (x @ up[j]))
                                  @ down[j])
        return jnp.sum(y * y)

    for name, a, b in zip(("x", "weights", "gate", "up", "down"), grads,
                          jax.grad(plain, argnums=(0, 1, 2, 3, 4))(
                              x, weights.data, gate, up, down)):
        assert _rel(a, b) < 5e-5, name
    # through the op, under the tape
    ty, seen = F.moe_experts(
        pt.to_tensor(np.asarray(x)), experts, weights,
        pt.to_tensor(np.asarray(up)), pt.to_tensor(np.asarray(down)),
        first_expert=first, w_gate=pt.to_tensor(np.asarray(gate)))
    np.testing.assert_allclose(ty.numpy(), y, atol=1e-6)
    np.testing.assert_array_equal(seen.numpy(), stats)


def test_the_ungated_experts_trace_no_gate():
    """Without ``w_gate`` the op is the relu-squared form as it was: two
    products a rung forward and five backward, no sigmoid anywhere."""
    S = jax.ShapeDtypeStruct
    args = (S((1, 2048, 64), jnp.bfloat16), S((1, 2048, 3), jnp.int32),
            S((1, 2048, 3), jnp.float32), S((4, 64, 32), jnp.float32),
            S((4, 32, 64), jnp.float32))

    def loss(x, experts, weights, up, down, *gate):
        y, _ = moe_ops._routed(x, experts, weights, up, down, *gate, first=0,
                               dot_dtype=jnp.bfloat16)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    plain = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 2, 3, 4)))(*args))
    rungs = len(moe_ops._ladder(2048, moe_ops.MIN_ROWS))
    assert rungs == 3
    assert plain.count("dot_general") == rungs * (2 + 5)
    assert "logistic" not in plain
    gated = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 2, 3, 4, 5)))(
        *args, S((4, 64, 32), jnp.float32)))
    assert gated.count("dot_general") == rungs * (3 + 8)
    assert "logistic" in gated


def test_gated_mlp_is_the_written_form():
    layer = nn.GatedMLP(16, 24)
    x = np.asarray(jax.random.normal(jax.random.key(9), (3, 5, 16)))
    w = {k: getattr(layer, k).weight.data
         for k in ("gate_proj", "up_proj", "down_proj")}
    want = R._gated_mlp(jnp.asarray(x).reshape(15, 16), w["gate_proj"],
                        w["up_proj"], w["down_proj"], _plain)
    np.testing.assert_allclose(layer(pt.to_tensor(x)).numpy().reshape(15, 16),
                               want, atol=1e-5)
    assert [n for n, _ in layer.named_parameters()] == [
        "gate_proj.weight", "up_proj.weight", "down_proj.weight"]
