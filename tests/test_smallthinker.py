"""SmallThinker on the training path, at a tiny size on the CPU, against the
benchmark's plain float32 reference (benchmark/reference/smallthinker.py,
which imports nothing of paddle_tpu): window layers with rotary positions
and global layers without positions in one stack, the sliding window as a
fact of the flash call, the router that reads the layer's input, the
ReLU-gated experts and the chip's share of them, and the model trained
through ``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` +
``loss.backward()``.
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
from paddle_tpu import amp, jit, monitor, nn                    # noqa: E402
from paddle_tpu import optimizer as opt                         # noqa: E402
from paddle_tpu.models.smallthinker import (                    # noqa: E402
    SmallThinkerConfig, SmallThinkerForCausalLM)
from paddle_tpu.ops import moe as moe_ops                       # noqa: E402
from paddle_tpu.ops.pallas import flash_attention               # noqa: E402
from paddle_tpu.ops.pallas import flash_attention_mod as flash_mod  # noqa: E402,E501
from benchmark.reference import smallthinker as R               # noqa: E402

HYPER = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8,
             weight_decay=0.1)


def _plain(spec, a, b):
    return jnp.einsum(spec, a, b)


def _model(seed=5, **kw):
    """(model holding the reference's seeded weights, cfg dict, weights)."""
    config = SmallThinkerConfig.tiny(**kw)
    cfg = dict(vars(config))
    model = SmallThinkerForCausalLM(config)
    weights = R.init_weights(cfg, seed)
    params = dict(model.named_parameters())
    assert set(params) == set(weights)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_value(weights[name])
    return model, cfg, weights


def _ids(rows=2, seq=24, vocab=256, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, seq)).astype(np.int32)


def _rel(got, ref):
    return np.abs(np.asarray(got) - np.asarray(ref)).max() \
        / (np.abs(np.asarray(ref)).max() + 1e-12)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        recompute):
    """24 positions under a window of 8: three of the four layers are
    windowed and rotated, layer 0 global and position-free."""
    model, cfg, weights = _model(recompute=recompute)
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == (0, 1, 1, 1)
    ids = _ids()
    logits = model(pt.to_tensor(ids))
    assert tuple(logits.shape) == (2, 24, 256)
    want = R.forward(cfg, weights, jnp.asarray(ids))
    np.testing.assert_allclose(logits.numpy(), want, atol=2e-6)
    batch = (jnp.asarray(ids),)
    loss = model.loss(logits, pt.to_tensor(ids))
    assert abs(float(loss.numpy()) - float(R.loss_fn(cfg, weights, batch))) \
        < 1e-5
    loss.backward()
    want_grad = jax.grad(lambda q: R.loss_fn(cfg, q, batch))(weights)
    assert len(R.compared_leaves(cfg)) == 2 + 4 * 8
    for name, p in model.named_parameters():
        assert _rel(p._grad, want_grad[name]) < 2e-5, name


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference():
    model, cfg, _ = _model(recompute=True)
    o = opt.AdamW(parameters=model.parameters(), **HYPER)

    def step(ids):
        with amp.auto_cast(dtype="bfloat16"):
            logits = model(ids)
        loss = model.loss(logits.astype("float32"), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    compiled = jit.to_static(step, models=[model], optimizers=[o])
    batches = [(_ids(seed=s),) for s in range(3)]
    got = [float(compiled(pt.to_tensor(b[0])).numpy()) for b in batches]
    want = R.train(cfg, HYPER, 5, batches)["loss"]
    # bf16 products against float32: the losses agree to bf16's rounding
    np.testing.assert_allclose(got, want, rtol=3e-3)


def test_config_reads_a_period_the_share_and_the_published_lists():
    c = SmallThinkerConfig()
    assert (c.num_hidden_layers, c.hidden_size, c.head_dim) == (52, 2560, 128)
    assert c.sliding_window_layout == c.rope_layout == (0, 1, 1, 1) * 13
    assert c.moe_num_primary_experts_published == 64
    # the published 52 entries beside 8 layers: the first 8 are read
    cut = SmallThinkerConfig(num_hidden_layers=8, moe_num_primary_experts=8,
                             moe_num_primary_experts_published=64,
                             rope_layout=[0, 1, 1, 1] * 13,
                             sliding_window_layout=[0, 1, 1, 1] * 13)
    assert cut.rope_layout == (0, 1, 1, 1, 0, 1, 1, 1)
    with pytest.raises(ValueError, match="published"):
        SmallThinkerConfig(moe_num_primary_experts=8, first_expert_held=60,
                           moe_num_primary_experts_published=64)
    with pytest.raises(ValueError, match="rope_layout"):
        SmallThinkerConfig(num_hidden_layers=8, rope_layout=[0, 1, 1, 1])
    with pytest.raises(ValueError, match="router"):
        SmallThinkerConfig(norm_topk_prob=False)
    model, _, _ = _model()
    kinds = [(b.self_attn.window, b.self_attn.rope_theta)
             for b in model.layers]
    assert kinds == [(None, None)] + [(8, 10000.0)] * 3
    names = [n for n, _ in model.layers[1].named_parameters()]
    assert sorted(names) == sorted([
        "input_layernorm.weight", "post_attention_layernorm.weight",
        "self_attn.q_proj.weight", "self_attn.k_proj.weight",
        "self_attn.v_proj.weight", "self_attn.o_proj.weight",
        "block_sparse_moe.router.weight", "block_sparse_moe.experts_gate",
        "block_sparse_moe.experts_up", "block_sparse_moe.experts_down"])


# -- the window: the rule, the kernels, the counts ---------------------------

@pytest.mark.parametrize("length,window", [(24, 8), (24, 1), (9, 24),
                                           (16, 16)])
def test_the_window_is_the_written_rule_on_every_pair(length, window):
    mask = flash_mod.sliding_window_mask(length, window)
    for i in range(length):
        for j in range(length):
            assert mask[i, j] == (j <= i and i - j < window), (i, j)
    np.testing.assert_array_equal(
        mask, R.allowed(jnp.arange(length), jnp.arange(length), window))
    # a row sees itself and the window - 1 positions before it
    assert mask.sum(1).tolist() == [min(i + 1, window)
                                    for i in range(length)]


def _dense(q, k, v, window):
    mask = jnp.asarray(flash_mod.sliding_window_mask(q.shape[2], window))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


KERNEL_CASES = [      # (length, window, block_q, block_k)
    (64, 16, 16, 16),      # the window a tile
    (64, 32, 16, 16),      # ... two tiles: the diagonal's tiles unrolled
    (64, 20, 16, 16),      # no multiple of the tile
    (70, 20, 16, 16),      # ... nor is the length
    (64, 5, 16, 16),       # narrower than a tile
    (64, 40, 16, 32),      # block_q < block_k
    (64, 40, 32, 16),      # block_q > block_k
    (96, 33, 16, 16),
    (20, 33, 16, 16),      # the length below the window
    (33, 33, 16, 16),      # ... at it
    (128, 100, 512, 1024),     # the defaults: one tile
]


@pytest.mark.parametrize("length,window,block_q,block_k", KERNEL_CASES)
def test_window_kernels_match_dense_masked_attention(length, window, block_q,
                                                     block_k):
    """Interpret mode, float32: forward and all three gradients, q/k 24
    wide and v 16."""
    key = jax.random.key(length * 7 + window)
    q, k, v, ct = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, 2, length, d))
                   for i, d in enumerate((24, 24, 16, 16)))

    def kernels(q, k, v):
        return flash_mod._flash_win(q, k, v, window, None, block_q, block_k)

    np.testing.assert_allclose(kernels(q, k, v), _dense(q, k, v, window),
                               atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, window) * ct),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-6, err_msg=f"d{name}")


@pytest.mark.parametrize("length,window,block_q,block_k", KERNEL_CASES)
def test_tile_counts_under_a_window_are_a_brute_force_count(
        length, window, block_q, block_k):
    bq, bk = flash_mod._clamped_blocks(block_q, block_k, length, length)
    geom = dict(block_q=bq, block_k=bk, sq=length, sk=length, causal=True,
                window=window)
    tiles, masked = flash_mod._tile_counts(3, **geom)
    n_q, n_k = -(-length // bq), -(-length // bk)
    padded = np.zeros((n_q * bq, n_k * bk), bool)
    padded[:length, :length] = flash_mod.sliding_window_mask(length, window)
    by_tile = padded.reshape(n_q, bq, n_k, bk)
    holds, whole = by_tile.any((1, 3)), by_tile.all((1, 3))
    assert tiles == 3 * int(holds.sum())
    assert masked == 3 * int((holds & ~whole).sum())
    # the backward kernel's bounds walk the same tiles, k-block by k-block
    walked = np.zeros((n_q, n_k), bool)
    for j in range(n_k):
        start, _, _, end = flash_mod._q_tile_ranges(np, j, **geom)
        assert flash_mod._q_tile_ranges(
            np, j, **dict(geom, window=None))[3] is None
        walked[int(start):int(end), j] = True
    np.testing.assert_array_equal(walked, holds)


def test_tile_counts_at_the_cells_shape_are_the_issues():
    """28 heads x 16,384 rows at 512 x 512 under a window of 4,096: 252 of
    1,024 tiles a head (36 in the first eight q-blocks, then nine each),
    56 of them masked (32 on the diagonal, 24 on the window's edge); the
    causal call 528."""
    bq, bk = flash_mod._blocks_that_fit(16384, 128, 128, 2, 512, 1024)
    assert (bq, bk) == (512, 512)
    assert flash_mod._single_buffered(16384, 128, 128, 2)
    geom = dict(block_q=512, block_k=512, sq=16384, sk=16384, causal=True)
    assert flash_mod._tile_counts(28, window=4096, **geom) == (28 * 252,
                                                               28 * 56)
    assert flash_mod._tile_counts(28, **geom) == (28 * 528, 28 * 32)
    assert abs(100 * 252 / 1024 - 24.609375) < 1e-9
    # every program masks its diagonal tile outside the loop, windowed too
    assert flash_mod._crossed_tiles(512, 512, window=4096, **geom) == 1
    assert flash_mod._crossed_tiles(512, 512, window=600, **geom) is None
    # allowed pairs a head, row by row: the issue's 58,722,304 of
    # 134,225,920
    seen = np.minimum(np.arange(16384) + 1, 4096)
    assert int(seen.sum()) == 58722304
    assert int((np.arange(16384) + 1).sum()) == 134225920


def test_the_dispatch_counts_the_path_and_the_tiles_and_refuses_a_mix():
    q = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(3),
                                                  (1, 2, 64, 16))))
    before = monitor.snapshot("flash_attention")
    got = flash_attention(q, q, q, causal=True, window=24, force=True,
                          block_q=16, block_k=16)
    plain = flash_attention(q, q, q, causal=True, window=24)   # sdpa, dense
    np.testing.assert_allclose(got.numpy(), plain.numpy(), atol=2e-6)
    np.testing.assert_allclose(
        got.numpy(), _dense(q.data, q.data, q.data, 24), atol=2e-6)
    after = monitor.snapshot("flash_attention")

    def gained(name):
        return after.get("flash_attention." + name, 0) \
            - before.get("flash_attention." + name, 0)

    assert gained("kernel_traced") == 1 and gained("xla_traced") == 1
    # two heads, four q-blocks of 16 under a window of 24: 1 + 2 + 3 + 3
    # of 16 tiles a head, each crossed by the diagonal or the window's edge
    assert gained("tiles") == 2 * 9 and gained("tiles_masked") == 2 * 9
    assert gained("tiles_skipped") == 2 * 7
    for kw in (dict(causal=False), dict(attn_mask=q, causal=True),
               dict(diffusion_block=4, causal=False),
               dict(causal=True, window=0)):
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, q, q, **{"window": 24, **kw})
    # a window that holds the whole sequence is a causal call
    text = str(jax.make_jaxpr(lambda a: flash_attention(
        pt.to_tensor(a), pt.to_tensor(a), pt.to_tensor(a), causal=True,
        window=64, force=True).data)(q.data))
    assert "flash_fwd" in text and "flash_win" not in text


# a call without a window traces what it traced before this PR: sha256 of
# the jaxpr's text of value-and-gradients, the digests tests/test_sdar_moe.py
# holds the other cells' call forms to; the 16k causal form is the global
# layers' call, taken at this PR's parent; retaken at PR 42 with those
# (e256fce's 398076cef3b873b6 and f00871eae9b4c0a4 plus the three ``name``
# equations of the saved results, nothing else)
PARENT_JAXPRS = {
    "nemotron": ((1, 32, 8192, 128), "edb2f8e1d4cb1eae"),
    "global_16k": ((1, 28, 16384, 128), "1ceb340bc4a830ff"),
}


def _value_and_grads_text(shape, window=None):
    S = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    bq, bk = flash_mod._blocks_that_fit(shape[2], shape[3], shape[3], 2,
                                        512, 1024)

    def loss(q, k, v):
        if window is not None:
            out = flash_mod._flash_win(q, k, v, window, None, bq, bk)
        else:
            out = flash_mod._flash(q, k, v, None, None,
                                   jnp.zeros((2,), jnp.int32), True, None,
                                   bq, bk, 0.0)
        return jnp.sum(out.astype(jnp.float32))

    return str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        S, S, S))


@pytest.mark.parametrize("form", sorted(PARENT_JAXPRS))
def test_a_call_without_a_window_traces_the_parents_kernels(form):
    shape, digest = PARENT_JAXPRS[form]
    text = _value_and_grads_text(shape)
    assert text.count("pallas_call") == 2
    assert "name=flash_fwd" in text and "name=flash_bwd" in text
    assert "flash_win" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_the_windowed_call_is_two_kernels_of_its_own_name_and_no_mask():
    """Nothing of 16,384 x 16,384 and no mask operand: every array of the
    traced program has at most one axis of 16,384 rows."""
    import re
    text = _value_and_grads_text((1, 28, 16384, 128), window=4096)
    assert text.count("pallas_call") == 2
    assert "name=flash_win_fwd" in text and "name=flash_win_bwd" in text
    for shape in re.findall(r"\w+\[([\d,]+)\]", text):
        dims = [int(d) for d in shape.split(",")]
        assert sum(d >= 4096 for d in dims) <= 1, shape


# -- the two kinds of attention layer ----------------------------------------

def _attention_layer(seed=11, **kw):
    layer = nn.GroupedQueryAttention(64, 4, 2, 16, **kw)
    key = jax.random.key(seed)
    for i, (_, p) in enumerate(layer.named_parameters()):
        p.set_value(0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                            tuple(p.shape)))
    return layer


@pytest.mark.parametrize("force", [False, True], ids=["sdpa", "kernels"])
@pytest.mark.parametrize("windowed", [0, 1], ids=["global", "window"])
def test_an_attention_layer_of_each_kind_is_the_references(windowed, force):
    layer = _attention_layer(**(dict(window=8, rope_theta=1e4)
                                if windowed else {}))
    cfg = dict(head_dim=16, num_attention_heads=4, num_key_value_heads=2,
               rope_theta=1e4, sliding_window_size=8,
               sliding_window_layout=[windowed], rope_layout=[windowed])
    w = {k: p.data for k, p in layer.named_parameters()}
    x = np.asarray(jax.random.normal(jax.random.key(12), (1, 40, 64)))
    got = layer(pt.to_tensor(x), force_flash=force).numpy()[0]
    want = R._attention(cfg, w, jnp.asarray(x[0]), 0, _plain)
    np.testing.assert_allclose(got, want, atol=3e-6)


def test_a_global_layer_knows_no_positions_and_a_window_row_no_key_at_w():
    x = np.asarray(jax.random.normal(jax.random.key(13), (1, 24, 64)))
    # global, position-free: the last row's output is the same whatever
    # the order of the keys before it
    layer = _attention_layer()
    text = str(jax.make_jaxpr(lambda a: layer(pt.to_tensor(a)).data)(x))
    assert "cos" not in text
    shuffled = x.copy()
    shuffled[0, :23] = x[0, np.random.default_rng(0).permutation(23)]
    np.testing.assert_allclose(layer(pt.to_tensor(shuffled)).numpy()[0, 23],
                               layer(pt.to_tensor(x)).numpy()[0, 23],
                               atol=2e-6)
    # windowed and rotated: row i sees key i - W + 1 and not key i - W;
    # positions matter
    layer = _attention_layer(window=8, rope_theta=1e4)
    base = layer(pt.to_tensor(x)).numpy()[0]
    assert np.abs(layer(pt.to_tensor(shuffled)).numpy()[0, 23]
                  - base[23]).max() > 1e-4
    moved = x.copy()
    moved[0, 10] += 1.0
    delta = np.abs(layer(pt.to_tensor(moved)).numpy()[0] - base).max(-1)
    assert delta[:10].max() == 0.0          # causal
    assert delta[10:18].min() > 0.0         # rows 10 .. 17 see key 10
    assert delta[18:].max() == 0.0          # row 18 = 10 + W does not


# -- the router ahead of attention, the ReLU gate, the share ------------------

def test_the_router_reads_the_layers_input():
    """The chosen experts are those of the reference's router on the
    block's input; the scale of the norm in front of the experts moves the
    experts' input and not the choice."""
    model, cfg, weights = _model()
    block = model.layers[1]
    x = np.asarray(jax.random.normal(jax.random.key(14), (1, 24, 64)))
    seen = {}
    route = moe_ops.moe_route

    def spy(inp, *a, **kw):
        out = route(inp, *a, **kw)
        seen["input"], seen["experts"] = inp.numpy(), out[1].numpy()
        return out

    moe_ops.moe_route = spy
    try:
        base = block(pt.to_tensor(x)).numpy()
        first = dict(seen)
        block.post_attention_layernorm.weight.set_value(
            3.0 * np.ones(64, np.float32))
        scaled = block(pt.to_tensor(x)).numpy()
    finally:
        moe_ops.moe_route = route
    np.testing.assert_array_equal(first["input"], x)
    chosen, _ = R.route(cfg, jnp.asarray(x[0]),
                        weights["layers.1.block_sparse_moe.router.weight"])
    np.testing.assert_array_equal(first["experts"][0], chosen)
    np.testing.assert_array_equal(seen["experts"], first["experts"])
    assert np.abs(scaled - base).max() > 1e-4
    # without router_input the layer routes by what the experts read
    layer = block.block_sparse_moe
    u = pt.to_tensor(x)
    np.testing.assert_array_equal(layer(u).numpy(),
                                  layer(u, router_input=u).numpy())


def _experts_args(key, tokens=40, d=32, f=16, held=4, published=8, k=3):
    x, gate, up = (0.5 * jax.random.normal(jax.random.fold_in(key, i), s)
                   for i, s in enumerate(((tokens, d), (held, d, f),
                                          (held, d, f))))
    down = 0.5 * jax.random.normal(jax.random.fold_in(key, 3), (held, f, d))
    logits = jax.random.normal(jax.random.fold_in(key, 4),
                               (tokens, published))
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, -1), k)
    return x, experts.astype(jnp.int32), weights, up, down, gate


def _plain_experts(x, experts, weights, up, down, gate, act):
    out = jnp.zeros_like(x)
    for e in range(up.shape[0]):
        g = jnp.sum(jnp.where(experts == e, weights, 0.0), -1)
        out = out + g[:, None] * ((act(x @ gate[e]) * (x @ up[e])) @ down[e])
    return out


@pytest.mark.parametrize("activation", ["relu", "silu"])
def test_gated_experts_forward_and_backward_are_the_plain_forms(activation):
    args = _experts_args(jax.random.key(31))
    act = getattr(jax.nn, activation)

    def program(x, weights, up, down, gate):
        y, _ = moe_ops.moe_experts(
            pt.to_tensor(x), pt.to_tensor(args[1]), pt.to_tensor(weights),
            pt.to_tensor(up), pt.to_tensor(down), w_gate=pt.to_tensor(gate),
            activation=activation)
        return y.data

    def plain(x, weights, up, down, gate):
        return _plain_experts(x, args[1], weights, up, down, gate, act)

    diff = (args[0],) + args[2:]
    np.testing.assert_allclose(program(*diff), plain(*diff), atol=2e-6)
    ct = jax.random.normal(jax.random.key(32), args[0].shape)
    got = jax.grad(lambda *a: jnp.sum(program(*a) * ct), range(5))(*diff)
    want = jax.grad(lambda *a: jnp.sum(plain(*a) * ct), range(5))(*diff)
    for name, a, b in zip(("x", "weights", "up", "down", "gate"), got, want):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=5e-6, err_msg=name)
    with pytest.raises(ValueError, match="activation"):
        moe_ops.moe_experts(*(pt.to_tensor(a) for a in args[:5]),
                            activation="relu")
    with pytest.raises(ValueError, match="activation"):
        nn.RoutedMoE(32, 16, 8, 3, activation="relu")


# sha256 of the jaxpr of value-and-gradients of the SiLU-gated experts (the
# joyai and sdar cells' form), taken at this PR's parent: the ReLU gate is
# an argument beside it and moves nothing of it
SILU_EXPERTS_JAXPR = "6acdbdaff5b2c504"


def test_the_silu_gated_experts_trace_what_they_traced():
    args = _experts_args(jax.random.key(33), tokens=1024)

    def loss(x, weights, up, down, gate):
        y, _ = moe_ops.moe_experts(
            pt.to_tensor(x), pt.to_tensor(args[1]), pt.to_tensor(weights),
            pt.to_tensor(up), pt.to_tensor(down), w_gate=pt.to_tensor(gate))
        return jnp.sum(y.data)

    text = str(jax.make_jaxpr(jax.value_and_grad(loss, range(5)))(
        args[0], *args[2:]))
    assert "logistic" in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        SILU_EXPERTS_JAXPR


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """8 shares of 8 experts of 64, top 6 (the configuration's cut), routed
    by another tensor than the experts read: the parts add up to what the
    layer that holds all 64 gives (no shared expert to count once), in the
    program and in the reference."""
    key = jax.random.key(21)
    x = 0.5 * jax.random.normal(key, (2, 24, 64))
    m = 0.5 * jax.random.normal(jax.random.fold_in(key, 99), (2, 24, 64))
    kind = dict(gated=True, scoring="softmax", activation="relu")
    whole = nn.RoutedMoE(64, 32, 64, 6, **kind)
    for i, (_, p) in enumerate(whole.named_parameters()):
        p.set_value(0.2 * jax.random.normal(jax.random.fold_in(key, i + 1),
                                            tuple(p.shape)))
    xt, mt = (pt.to_tensor(np.asarray(t)) for t in (x, m))
    want = whole(mt, router_input=xt).numpy()
    w = {k: p.data for k, p in whole.named_parameters()}
    cfg = dict(moe_num_primary_experts=64,
               moe_num_primary_experts_published=64,
               moe_num_active_primary_experts=6)
    flat = (x.reshape(48, 64), m.reshape(48, 64))
    ref_whole = R._moe(cfg, w, *flat, _plain)
    np.testing.assert_allclose(want.reshape(48, 64), ref_whole, atol=2e-6)
    total, ref_total = 0.0, 0.0
    for first in range(0, 64, 8):
        share = nn.RoutedMoE(64, 32, 64, 6,
                             experts_held=range(first, first + 8), **kind)
        share.router.weight.set_value(w["router.weight"])
        held = {k: w[k][first:first + 8]
                for k in ("experts_gate", "experts_up", "experts_down")}
        for k, v in held.items():
            getattr(share, k).set_value(v)
        total = total + share(mt, router_input=xt).numpy()
        ref_total = ref_total + R._moe(
            dict(cfg, moe_num_primary_experts=8, first_expert_held=first),
            dict(held, **{"router.weight": w["router.weight"]}), *flat,
            _plain)
    np.testing.assert_allclose(total, want, atol=3e-6)
    np.testing.assert_allclose(ref_total, ref_whole, atol=3e-6)


# -- set-up: the window layers' kernels are lowered once a module -----------

_MAKE_ENTRY = jit.StaticFunction._make_entry


def _lowered_step(monkeypatch, layers):
    """The StableHLO of a training step of a stack of ``layers`` recomputed
    blocks at 512 positions under a window of 128, lowered for a TPU from
    this process (nothing is compiled), and its ``pallas_call`` counts."""
    from paddle_tpu.ops import pallas as P

    class Lowered(Exception):
        pass

    def make_entry(self, *args, **kwargs):
        entry = _MAKE_ENTRY(self, *args, **kwargs)
        jitted = entry["jitted"]

        def lower(state, arrays):
            shapes = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype),
                (state, arrays))
            traced = jitted.trace(*shapes)
            raise Lowered(monitor.xla.count_pallas(traced.jaxpr),
                          traced.lower(lowering_platforms=("tpu",)).as_text())
        entry["jitted"] = lower
        return entry

    monkeypatch.setattr(jit.StaticFunction, "_make_entry", make_entry)
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    pt.seed(0)
    model = SmallThinkerForCausalLM(SmallThinkerConfig.tiny(
        num_hidden_layers=layers, hidden_size=128, head_dim=128,
        num_attention_heads=2, num_key_value_heads=1,
        sliding_window_size=128, recompute=True))
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def step(ids):
        with amp.auto_cast(dtype="bfloat16"):
            logits = model(ids)
        loss = model.loss(logits.astype("float32"), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    with pytest.raises(Lowered) as e:
        jit.to_static(step, models=[model], optimizers=[o])(
            pt.to_tensor(_ids(rows=1, seq=512)))
    return e.value.args


def test_window_kernel_instances_do_not_grow_with_the_window_layers(
        monkeypatch):
    """Four layers hold three window layers and eight hold six: each has a
    forward and a backward call of the windowed kernels (no recomputed
    forward since PR 42: the block's checkpoint keeps the call's results),
    behind module-level jits that the module holds once a form, whatever
    the number of layers. A global layer's causal call is two instances a
    layer."""
    import re
    before = monitor.snapshot("flash_attention").get(
        "flash_attention.kernel_traced", 0)
    seen = {}
    for layers in (4, 8):
        (instances, _), text = _lowered_step(monkeypatch, layers)
        win = {name: len(re.findall(r"func\.func private @%s\b" % name,
                                    text))
               for name in ("_win_fwd", "_win_bwd")}
        calls = len(re.findall(r"call @_win_(fwd|bwd)", text))
        seen[layers] = (instances, win, calls)
    assert monitor.snapshot("flash_attention")[
        "flash_attention.kernel_traced"] - before == 4 + 8
    (few, win4, calls4), (many, win8, calls8) = seen[4], seen[8]
    assert win4 == win8 == {"_win_fwd": 1, "_win_bwd": 1}, seen
    assert (calls4, calls8) == (2 * 3, 2 * 6)
    # the jaxpr's count: the windowed forms once each, the global layers'
    # two calls a layer and the experts' scatter kernel besides
    assert many - few == 2 * 1, seen
