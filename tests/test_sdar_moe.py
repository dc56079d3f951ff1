"""SDAR-MoE under block-diffusion training, at a tiny size on the CPU,
against the benchmark's plain float32 reference (benchmark/reference/
sdar_moe.py, which imports nothing of paddle_tpu): the three-part mask, the
flash kernels that take it as structure, grouped-query attention with head
norms and rotary positions, the soft-max router and the chip's share of the
experts, the masked-position loss, and the model trained through
``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``.
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
from paddle_tpu import amp, jit, monitor, nn, ops               # noqa: E402
from paddle_tpu import optimizer as opt                         # noqa: E402
from paddle_tpu.models.sdar_moe import (                        # noqa: E402
    SDARMoEConfig, SDARMoEForBlockDiffusion)
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.ops import moe as moe_ops                       # noqa: E402
from paddle_tpu.ops.pallas import flash_attention               # noqa: E402
from paddle_tpu.ops.pallas import flash_attention_mod as flash_mod  # noqa: E402,E501
from benchmark.reference import sdar_moe as R                   # noqa: E402

HYPER = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8,
             weight_decay=0.1)


def _plain(spec, a, b):
    return jnp.einsum(spec, a, b)


def _model(seed=5, **kw):
    """(model holding the reference's seeded weights, cfg dict, weights)."""
    config = SDARMoEConfig.tiny(**kw)
    cfg = dict(vars(config))
    model = SDARMoEForBlockDiffusion(config)
    weights = R.init_weights(cfg, seed)
    params = dict(model.named_parameters())
    assert set(params) == set(weights)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_value(weights[name])
    return model, cfg, weights


def _batch(rows=2, seq=24, block=4, vocab=256, seed=0):
    """(clean ids, noisy ids, weights) as the benchmark's family makes
    them: one noise level a block, weight 1 / t at a masked position."""
    rng = np.random.default_rng(seed)
    clean = rng.integers(0, vocab - 1, (rows, seq)).astype(np.int32)
    t = np.repeat(rng.uniform(0.05, 1.0, (rows, seq // block)), block, 1)
    masked = rng.random((rows, seq)) < t
    return (clean, np.where(masked, vocab - 1, clean).astype(np.int32),
            np.where(masked, 1.0 / t, 0.0).astype(np.float32))


def _rel(got, ref):
    return np.abs(np.asarray(got) - np.asarray(ref)).max() \
        / (np.abs(np.asarray(ref)).max() + 1e-12)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        recompute):
    model, cfg, weights = _model(recompute=recompute)
    clean, noisy, w = _batch()
    logits = model(pt.to_tensor(noisy), pt.to_tensor(clean))
    assert tuple(logits.shape) == (2, 24, 256)   # the noisy copy's rows
    want = R.forward(cfg, weights, jnp.asarray(noisy), jnp.asarray(clean))
    np.testing.assert_allclose(logits.numpy(), want, atol=2e-6)
    batch = tuple(jnp.asarray(a) for a in (clean, noisy, w))
    loss = model.loss(logits, pt.to_tensor(clean), pt.to_tensor(w))
    assert abs(float(loss.numpy()) - float(R.loss_fn(cfg, weights, batch))) \
        < 1e-5
    loss.backward()
    want_grad = jax.grad(lambda q: R.loss_fn(cfg, q, batch))(weights)
    assert len(R.compared_leaves(cfg)) == 2 + 2 * 8
    for name, p in model.named_parameters():
        assert _rel(p._grad, want_grad[name]) < 2e-5, name


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference():
    model, cfg, _ = _model(recompute=True)
    monitor.device_counters.reset()
    model = _model(recompute=True)[0]       # registers after the reset
    o = opt.AdamW(parameters=model.parameters(), **HYPER)

    def step(clean, noisy, w):
        with amp.auto_cast(dtype="bfloat16"):
            logits = model(noisy, clean)
        loss = model.loss(logits.astype("float32"), clean, w)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    compiled = jit.to_static(step, models=[model], optimizers=[o])
    batches = [_batch(seed=s) for s in range(3)]
    got = [float(compiled(*(pt.to_tensor(a) for a in b)).numpy())
           for b in batches]
    want = R.train(cfg, HYPER, 5, batches)["loss"]
    # bf16 products against float32: the losses agree to bf16's rounding
    np.testing.assert_allclose(got, want, rtol=3e-3)
    # the step counted its own masked positions, on the device
    seen = monitor.device_counters.read("diffusion.")
    assert seen == {"diffusion.masked_rows":
                    sum(int((b[2] > 0).sum()) for b in batches),
                    "diffusion.steps": 3}


def test_the_clean_copy_gives_keys_and_no_logits_and_no_row_sees_ahead():
    """A clean token of a LATER block moves no logit of an earlier block;
    a clean token of an earlier block moves the later blocks' logits; a
    noisy token moves its own block's logits alone."""
    model, _, _ = _model()
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


# -- the mask ----------------------------------------------------------------

def test_the_mask_is_the_written_rule_on_every_pair():
    """L = 16, B = 4: every (r, s) of the 32 x 32 pairs against the rule
    as ISSUE 33 writes it, and the reference's own."""
    length, block = 16, 4
    got = flash_mod.block_diffusion_mask(length, block)
    assert got.shape == (32, 32) and got.dtype == np.bool_
    for r in range(32):
        for s in range(32):
            c_r, c_s = r // length, s // length
            b_r, b_s = (r % length) // block, (s % length) // block
            want = (c_s == 1 and b_s < b_r) or (c_s == c_r and b_s == b_r)
            assert got[r, s] == want, (r, s)
    at = jnp.arange(32)
    np.testing.assert_array_equal(got, R.allowed(at, at, length, block))
    # a clean row: block-causal over the clean copy, nothing of the noisy
    assert got[16 + 5].tolist() == [False] * 16 + [True] * 8 + [False] * 8
    # a noisy row: the clean blocks before its own, its own noisy block
    assert got[5].tolist() == [False] * 4 + [True] * 4 + [False] * 8 \
        + [True] * 4 + [False] * 12
    assert int(got.sum()) == length * length + length * block


# -- the kernels under the structure ----------------------------------------

def _dense(q, k, v, length, block):
    mask = jnp.asarray(flash_mod.block_diffusion_mask(length, block))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


KERNEL_CASES = [      # (L, B, block_q, block_k)
    (32, 4, 16, 16),       # L a multiple of the tile
    (40, 4, 16, 16),       # ... and not: each copy is padded to 48
    (64, 32, 32, 32),      # a diffusion block is a tile
    (96, 32, 32, 64),      # block_q < block_k, padded to 128
    (48, 4, 16, 8),        # block_q > block_k
    (48, 4, 8, 16),
    (64, 4, 512, 1024),    # the defaults: one tile a copy
]


@pytest.mark.parametrize("length,block,block_q,block_k", KERNEL_CASES)
def test_kernels_under_the_structure_match_dense_masked_attention(
        length, block, block_q, block_k):
    """Interpret mode, float32: forward and all three gradients, q/k 24
    wide and v 16."""
    key = jax.random.key(length * 7 + block)
    q, k, v, ct = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, 2, 2 * length, d))
                   for i, d in enumerate((24, 24, 16, 16)))
    shift = block.bit_length() - 1

    def kernels(q, k, v):
        return flash_mod._flash_bd(q, k, v, shift, None, block_q, block_k)

    np.testing.assert_allclose(kernels(q, k, v),
                               _dense(q, k, v, length, block), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, length, block) * ct),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-6, err_msg=f"d{name}")


@pytest.mark.parametrize("length,block,block_q,block_k", KERNEL_CASES)
def test_tile_counts_are_a_brute_force_count_of_the_tiles_that_hold_a_pair(
        length, block, block_q, block_k):
    shift = block.bit_length() - 1
    bq, bk = flash_mod._bd_blocks(block_q, block_k, length, shift)
    lp = flash_mod._bd_padded(length, bq, bk)
    tiles, masked, whole = flash_mod._bd_tile_counts(
        3, length, block_q=bq, block_k=bk, shift=shift)
    # the layout the kernels walk: each copy padded to whole tiles
    dense = flash_mod.block_diffusion_mask(lp, block)
    by_tile = dense.reshape(2 * lp // bq, bq, 2 * lp // bk, bk)
    holds = by_tile.any((1, 3))
    assert whole == 3 * holds.size
    assert tiles == 3 * int(holds.sum())
    # over the clean copy's keys a tile that holds a pair and is not all
    # pairs runs the masked body; a noisy block's own tiles always do
    clean_keys = by_tile[:, :, lp // bk:]
    crossed = int((clean_keys.any((1, 3)) & ~clean_keys.all((1, 3))).sum())
    own = (lp // bq) * max(1, bq // bk)
    assert masked == 3 * (crossed + own)


def test_tile_counts_at_the_cells_shape_are_the_issues():
    """32 heads x 2 x 8,192 rows at 512 x 512: n (n + 1) + n of 4 n^2 tiles
    a head, 3 n of them masked, n = 16."""
    bq, bk = flash_mod._blocks_that_fit(8192, 128, 128, 2, 512, 1024)
    assert flash_mod._bd_blocks(bq, bk, 8192, 2) == (512, 512)
    assert not flash_mod._single_buffered(8192, 128, 128, 2)
    tiles, masked, whole = flash_mod._bd_tile_counts(
        32, 8192, block_q=512, block_k=512, shift=2)
    assert (tiles, masked, whole) == (32 * 288, 32 * 48, 32 * 1024)
    assert abs(100 * tiles / whole - 28.125) < 1e-9


def test_the_dispatch_counts_the_path_and_the_tiles_and_refuses_a_mix():
    q = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(3),
                                                  (1, 2, 64, 16))))
    before = monitor.snapshot("flash_attention")
    got = flash_attention(q, q, q, diffusion_block=4, force=True,
                          block_q=16, block_k=16)
    plain = flash_attention(q, q, q, diffusion_block=4)      # sdpa, dense
    np.testing.assert_allclose(got.numpy(), plain.numpy(), atol=2e-6)
    after = monitor.snapshot("flash_attention")

    def gained(name):
        return after.get("flash_attention." + name, 0) \
            - before.get("flash_attention." + name, 0)

    assert gained("kernel_traced") == 1 and gained("xla_traced") == 1
    # two heads, two blocks of 16 a copy: 2 x 3 + 2 of 16 tiles a head
    assert gained("tiles") == 2 * 8 and gained("tiles_masked") == 2 * 6
    assert gained("tiles_skipped") == 2 * 8
    for kw in (dict(causal=True), dict(attn_mask=q), dict(diffusion_block=3),
               dict(diffusion_block=64)):
        with pytest.raises(ValueError, match="diffusion_block"):
            flash_attention(q, q, q, **{"diffusion_block": 4, **kw})
    # a causal call counts what it leaves out too
    before = after
    flash_attention(q, q, q, causal=True, force=True, block_q=16, block_k=16)
    after = monitor.snapshot("flash_attention")
    assert gained("tiles") == 2 * 10 and gained("tiles_skipped") == 2 * 6


# the three call forms the benchmark's other cells trace, lowered here as
# value-and-gradients of the kernels' custom_vjp: sha256 of the jaxpr's
# text, recomputed at PR 40, whose one backward kernel is meant to reach
# all of them (ea9585c's were 552400deb2309687, 1ce92ea17d73c217 and
# 0d43c90354e8ae13). A change to the kernels that is meant to reach those
# cells recomputes them; the block structure is not. Retaken at PR 42,
# which names the vjp-forward's three results: e256fce's texts
# (0bef64586abe9150, 398076cef3b873b6, 8d25934ee86c1597) with three ``name``
# equations more and the later variables' letters moved by them, nothing
# else (compared line by line with the letters taken out).
PARENT_JAXPRS = {
    "seq512": "a2968dc5da9e054d",
    "nemotron": "edb2f8e1d4cb1eae",
    "joyai": "60540556dedf1c9b",
}


def _call_forms():
    S, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    return {
        "seq512": ((S((16, 12, 512, 64), bf),) * 3
                   + (S((16, 1, 1, 512), jnp.float32),), False),
        "nemotron": ((S((1, 32, 8192, 128), bf),) * 3, True),
        "joyai": ((S((1, 32, 8192, 192), bf),) * 2
                  + (S((1, 32, 8192, 128), bf),), True),
    }


@pytest.mark.parametrize("form", sorted(PARENT_JAXPRS))
def test_a_call_without_the_structure_traces_the_parents_kernels(form):
    args, causal = _call_forms()[form]

    def value_and_grads(q, k, v, *mask):
        bq, bk = flash_mod._blocks_that_fit(q.shape[2], q.shape[3],
                                            v.shape[3], 2, 512, 1024)
        mode = flash_mod._mask_mode(mask[0].shape if mask else None,
                                    *q.shape[:3], k.shape[2])
        m = flash_mod._canon_mask(mask[0]) if mask else None

        def loss(q, k, v):
            return jnp.sum(flash_mod._flash(
                q, k, v, m, mode, jnp.zeros((2,), jnp.int32), causal, None,
                bq, bk, 0.0).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = str(jax.make_jaxpr(value_and_grads)(*args))
    assert text.count("pallas_call") == 2
    assert text.count("name[name=flash_") == 3
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_JAXPRS[form]


def test_the_structured_call_holds_no_array_of_both_copies_squared():
    """Nothing of 2L x 2L, and no mask operand: every array of the traced
    program has at most one axis of 2 L (or L) rows."""
    import re
    S = jax.ShapeDtypeStruct((1, 2, 2048, 16), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_mod._flash_bd(
            q, k, v, 2, None, 256, 256).astype(jnp.float32)),
        argnums=(0, 1, 2)))(S, S, S))
    assert text.count("pallas_call") == 2
    for shape in re.findall(r"\w+\[([\d,]+)\]", text):
        dims = [int(d) for d in shape.split(",")]
        assert sum(d >= 1024 for d in dims) <= 1, shape


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
    want = whole(pt.to_tensor(np.asarray(u))).numpy()
    w = {k: p.data for k, p in whole.named_parameters()}
    cfg = dict(num_experts=16, num_experts_published=16,
               num_experts_per_tok=3)
    ref_whole = R._moe(cfg, w, u.reshape(48, 64), _plain)
    np.testing.assert_allclose(want.reshape(48, 64), ref_whole, atol=2e-6)
    total, ref_total = 0.0, 0.0
    for first in range(0, 16, 4):
        share = nn.RoutedMoE(64, 32, 16, 3, gated=True, scoring="softmax",
                             experts_held=range(first, first + 4))
        share.router.weight.set_value(w["router.weight"])
        held = {k: w[k][first:first + 4]
                for k in ("experts_gate", "experts_up", "experts_down")}
        for k, v in held.items():
            getattr(share, k).set_value(v)
        total = total + share(pt.to_tensor(np.asarray(u))).numpy()
        ref_total = ref_total + R._moe(
            dict(cfg, num_experts=4, first_expert_held=first),
            dict(held, **{"router.weight": w["router.weight"]}),
            u.reshape(48, 64), _plain)
    np.testing.assert_allclose(total, want, atol=3e-6)
    np.testing.assert_allclose(ref_total, ref_whole, atol=3e-6)


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
