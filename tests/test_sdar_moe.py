"""SDAR-MoE under block-diffusion training, at a tiny size on the CPU,
against the benchmark's plain float32 reference (benchmark/reference/
sdar_moe.py, which imports nothing of paddle_tpu): the three-part mask, the
flash kernels that take it as structure, grouped-query attention with head
norms and rotary positions, the soft-max router and the chip's share of the
experts, the masked-position loss, and the model trained through
``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``.
The contract with the reference is tests/family_contract.py's; the mask and
the flash kernels under it are in tests/test_flash_block_diffusion.py.
"""
import hashlib
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
from paddle_tpu import monitor, nn, ops                         # noqa: E402
from paddle_tpu.models.sdar_moe import (                        # noqa: E402
    SDARMoEConfig, SDARMoEForBlockDiffusion)
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.ops import moe as moe_ops                       # noqa: E402
from benchmark.reference import sdar_moe as R                   # noqa: E402
from family_contract import (Family, Reference,                 # noqa: E402
                             check_expert_shares_add_up,
                             check_matches_reference,
                             check_trains_through_to_static,
                             plain as _plain, routed_share)


def _batch(rows=2, seq=24, block=4, vocab=256, seed=0):
    """(clean ids, noisy ids, weights) as the benchmark's family makes
    them: one noise level a block, weight 1 / t at a masked position."""
    rng = np.random.default_rng(seed)
    clean = rng.integers(0, vocab - 1, (rows, seq)).astype(np.int32)
    t = np.repeat(rng.uniform(0.05, 1.0, (rows, seq // block)), block, 1)
    masked = rng.random((rows, seq)) < t
    return (clean, np.where(masked, vocab - 1, clean).astype(np.int32),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))


# the forward reads the noisy copy, then the clean one; the loss the clean
# ids and the weights
FAMILY = Family(
    R, SDARMoEForBlockDiffusion, SDARMoEConfig.tiny,
    batch=lambda seed: _batch(seed=seed),
    inputs=lambda batch: (batch[1], batch[0]),
    loss=lambda model, outputs, batch: model.loss(outputs[0], batch[0],
                                                  batch[2]))


@pytest.fixture(scope="module")
def reference():
    return Reference(FAMILY)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        reference, recompute):
    seen = check_matches_reference(reference, recompute)
    # the noisy copy's rows
    assert tuple(seen.outputs[0].shape) == (2, 24, 256)
    assert len(R.compared_leaves(seen.cfg)) == 2 + 2 * 8


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference(
        reference):
    monitor.device_counters.reset()     # the model registers after it
    # bf16 products against float32: the losses agree to bf16's rounding
    _, batches = check_trains_through_to_static(reference, rtol=3e-3)
    # the step counted its own masked positions, on the device
    seen = monitor.device_counters.read("diffusion.")
    assert seen == {"diffusion.masked_rows":
                    sum(int((b[2] > 0).sum()) for b in batches),
                    "diffusion.steps": 3}


def test_the_clean_copy_gives_keys_and_no_logits_and_no_row_sees_ahead(
        reference):
    """A clean token of a LATER block moves no logit of an earlier block;
    a clean token of an earlier block moves the later blocks' logits; a
    noisy token moves its own block's logits alone."""
    model, _, _ = reference.model()
    clean, noisy, _ = _batch(rows=1)

    def logits(noisy, clean):
        return model(pt.to_tensor(noisy), pt.to_tensor(clean)).numpy()[0]

    base = logits(noisy, clean)
    later = clean.copy()
    later[0, 20] = (later[0, 20] + 7) % 255         # block 5
    moved = np.abs(logits(noisy, later) - base).max(-1)
    assert moved[:24].max() == 0.0      # nothing before block 6 sees it
    early = clean.copy()
    early[0, 1] = (early[0, 1] + 7) % 255           # block 0
    moved = np.abs(logits(noisy, early) - base).max(-1)
    assert moved[:4].max() == 0.0 and moved[4:].min() > 0.0
    own = noisy.copy()
    own[0, 9] = (own[0, 9] + 7) % 255               # block 2 of the noisy copy
    moved = np.abs(logits(own, clean) - base).max(-1)
    assert moved[8:12].min() > 0.0
    assert moved[:8].max() == 0.0 and moved[12:].max() == 0.0


def test_config_checks_the_share_the_block_and_the_names():
    c = SDARMoEConfig()
    assert (c.num_experts_published, c.mask_token_id, c.block_length) == \
        (128, 151935, 4)
    with pytest.raises(ValueError, match="published"):
        SDARMoEConfig.tiny(first_expert_held=14)
    with pytest.raises(ValueError, match="norm_topk_prob"):
        SDARMoEConfig.tiny(norm_topk_prob=False)
    with pytest.raises(ValueError, match="power of two"):
        SDARMoEConfig.tiny(block_length=6)
    names = dict(SDARMoEForBlockDiffusion(SDARMoEConfig.tiny())
                 .named_parameters())
    for name in ("layers.0.self_attn.q_norm.weight",
                 "layers.0.self_attn.k_norm.weight",
                 "layers.1.mlp.router.weight", "layers.1.mlp.experts_gate",
                 "lm_head.weight", "embed_tokens.weight"):
        assert name in names, name
    assert not any("shared" in n or "bias" in n for n in names)
    assert tuple(names["layers.0.self_attn.q_norm.weight"].shape) == (16,)
    assert pt.models.SDARMoEConfig is SDARMoEConfig


# -- grouped-query attention with head norms and positions -------------------

def _attention_layer(seed=11, **kw):
    layer = nn.GroupedQueryAttention(64, 4, 2, 16, causal=False,
                                     qk_norm_epsilon=1e-6, rope_theta=1e4,
                                     diffusion_block=4, **kw)
    key = jax.random.key(seed)
    for i, (_, p) in enumerate(layer.named_parameters()):
        p.set_value(0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                            tuple(p.shape)) + (p.ndim == 1))
    return layer


@pytest.mark.parametrize("force", [False, True], ids=["sdpa", "kernels"])
def test_attention_with_head_norms_and_positions_is_the_references(force):
    layer = _attention_layer()
    cfg = dict(head_dim=16, num_attention_heads=4, num_key_value_heads=2,
               rms_norm_eps=1e-6, rope_theta=1e4, block_length=4)
    w = {k: p.data for k, p in layer.named_parameters()}
    x = np.asarray(jax.random.normal(jax.random.key(12), (1, 48, 64)))
    at = np.concatenate([np.arange(24), np.arange(24)]).astype(np.int32)
    got = layer(pt.to_tensor(x), positions=pt.to_tensor(at),
                force_flash=force).numpy()[0]
    want = R._attention(cfg, w, jnp.asarray(x[0]), _plain)
    np.testing.assert_allclose(got, want, atol=3e-6)
    q, k, v = layer.qkv(pt.to_tensor(x), pt.to_tensor(at))
    rq, rk, rv = R.qkv(cfg, w, jnp.asarray(x[0]), _plain)
    for mine, theirs in ((q, rq), (k, rk), (v, rv)):
        np.testing.assert_allclose(mine.numpy()[0],
                                   np.moveaxis(theirs, 1, 0), atol=3e-6)
    # both copies of a token stand at its position: the same rotation
    np.testing.assert_allclose(
        F.rotary_embedding(pt.to_tensor(x), pt.to_tensor(at), theta=1e4,
                           interleaved=False).numpy()[0, 24:],
        R.rotary(jnp.asarray(x[0, 24:]), jnp.arange(24), 1e4), atol=3e-6)


def test_the_plain_grouped_query_attention_is_as_it_was():
    """No norm, no rotation, no parameter beyond the four projections; the
    structure cannot be asked for beside the causal mask."""
    layer = nn.GroupedQueryAttention(64, 4, 2, 16)
    assert [n for n, _ in layer.named_parameters()] == [
        "q_proj.weight", "k_proj.weight", "v_proj.weight", "o_proj.weight"]
    x = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(2),
                                                  (1, 24, 64))))
    text = str(jax.make_jaxpr(lambda a: layer(pt.to_tensor(a)).data)(x.data))
    assert "cos" not in text and "rsqrt" not in text
    with pytest.raises(ValueError, match="causal"):
        nn.GroupedQueryAttention(64, 4, 2, 16, diffusion_block=4)


# -- the soft-max router and the share ---------------------------------------

def test_softmax_router_is_the_references_and_the_sigmoid_one_is_as_it_was():
    x = np.asarray(jax.random.normal(jax.random.key(4), (2, 9, 32)))
    w = np.asarray(jax.random.normal(jax.random.key(5), (32, 16)))
    weights, experts = moe_ops.moe_route(pt.to_tensor(x), pt.to_tensor(w),
                                         top_k=3, scoring="softmax")
    cfg = dict(num_experts_per_tok=3)
    chosen, want = R.route(cfg, jnp.asarray(x.reshape(18, 32)),
                           jnp.asarray(w))
    np.testing.assert_array_equal(experts.numpy().reshape(18, 3), chosen)
    np.testing.assert_allclose(weights.numpy().reshape(18, 3), want,
                               atol=1e-6)
    np.testing.assert_allclose(weights.numpy().sum(-1), 1.0, atol=1e-6)
    # the weights are a soft-max over ALL 16 outputs, renormalised: the
    # written form
    p = jax.nn.softmax(jnp.asarray(x.reshape(18, 32) @ w), -1)
    top = np.sort(np.asarray(p), -1)[:, -3:][:, ::-1]
    np.testing.assert_allclose(weights.numpy().reshape(18, 3),
                               top / top.sum(-1, keepdims=True), atol=1e-5)
    with pytest.raises(ValueError, match="scoring"):
        moe_ops.moe_route(pt.to_tensor(x), pt.to_tensor(w), scoring="tanh")

    def traced(**kw):
        return str(jax.make_jaxpr(lambda a, b: tuple(
            t.data for t in moe_ops.moe_route(pt.to_tensor(a),
                                              pt.to_tensor(b), top_k=3,
                                              scale=2.5, **kw)))(x, w))

    sigmoid = traced()
    assert sigmoid == traced(scoring="sigmoid")
    assert "logistic" in sigmoid and "exp" not in sigmoid.replace(
        "expand", "")
    assert "logistic" not in traced(scoring="softmax")
    # sha256 of the sigmoid form's jaxpr: as PR 38 left it (the pick a
    # select over the expert axis; f5b6dee9b9f28c16 with take_along_axis,
    # at ea9585c), so a change to the soft-max form cannot move this one
    assert hashlib.sha256(sigmoid.encode()).hexdigest()[:16] == \
        SIGMOID_ROUTE_JAXPR


SIGMOID_ROUTE_JAXPR = "137df24d8c66d935"


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """16 experts, top-3: the parts that all 4 shares of 4 experts give
    add up to what the layer that holds all 16 gives (no shared expert to
    count once), in the program and in the reference."""
    key = jax.random.key(21)
    u = 0.5 * jax.random.normal(key, (2, 24, 64))
    whole = nn.RoutedMoE(64, 32, 16, 3, gated=True, scoring="softmax")
    assert whole.e_score_correction_bias is None
    assert [n for n, _ in whole.named_parameters()] == [
        "experts_gate", "experts_up", "experts_down", "router.weight"]
    for i, (_, p) in enumerate(whole.named_parameters()):
        p.set_value(0.2 * jax.random.normal(jax.random.fold_in(key, i + 1),
                                            tuple(p.shape)))
    w = {k: p.data for k, p in whole.named_parameters()}

    def layer(first, n):
        return whole if n == 16 else routed_share(
            lambda held: nn.RoutedMoE(64, 32, 16, 3, gated=True,
                                      scoring="softmax", experts_held=held),
            w, first, n)

    check_expert_shares_add_up(
        R, w, layer, lambda first, n: dict(
            num_experts=n, num_experts_published=16, num_experts_per_tok=3,
            first_expert_held=first),
        u, experts=16, held=4)


# -- the loss -----------------------------------------------------------------

def test_the_loss_is_zero_without_weights_and_plain_cross_entropy_with_ones():
    z = np.asarray(jax.random.normal(jax.random.key(6), (2, 12, 32)))
    y = np.asarray(jax.random.randint(jax.random.key(7), (2, 12), 0, 32),
                   np.int32)
    zt, yt = pt.to_tensor(z), pt.to_tensor(y)
    zero = ops.loss.block_diffusion_loss(zt, yt, pt.to_tensor(
        np.zeros((2, 12), np.float32)))
    assert float(zero.numpy()) == 0.0
    ones = ops.loss.block_diffusion_loss(zt, yt, pt.to_tensor(
        np.ones((2, 12), np.float32)))
    # m = 1, t = 1: the plain mean cross entropy, position i against id i
    plain = ops.loss.cross_entropy(zt, yt)
    assert abs(float(ones.numpy()) - float(plain.numpy())) < 1e-6
    shifted = ops.loss.cross_entropy(zt[:, :-1], yt[:, 1:])
    assert abs(float(ones.numpy()) - float(shifted.numpy())) > 1e-3
    # float32 whatever the logits are, and linear in the weights
    w = np.asarray(jax.random.uniform(jax.random.key(8), (2, 12)))
    half = ops.loss.block_diffusion_loss(zt.astype("bfloat16"), yt,
                                         pt.to_tensor(w))
    assert half.dtype == jnp.float32
    full = ops.loss.block_diffusion_loss(zt, yt, pt.to_tensor(2 * w))
    one = ops.loss.block_diffusion_loss(zt, yt, pt.to_tensor(w))
    assert abs(float(full.numpy()) - 2 * float(one.numpy())) < 1e-6
