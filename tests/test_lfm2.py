"""LFM2-MoE on the training path, at a tiny size on the CPU, against the
benchmark's plain float32 reference (benchmark/reference/lfm2_moe.py,
which imports nothing of paddle_tpu): gated short-convolution layers
beside a grouped-query attention layer in one stack, the double-gated
convolution as one op with an XLA route and a kernel route, a dense layer
in front of sigmoid-routed gated experts and the chip's share of them, the
tied head, and the model trained through ``jit.to_static`` +
``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``.
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
from paddle_tpu import amp, jit, monitor, nn                    # noqa: E402
from paddle_tpu import optimizer as opt                         # noqa: E402
from paddle_tpu.models.lfm2 import (                            # noqa: E402
    Lfm2MoeConfig, Lfm2MoeForCausalLM)
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.ops import pallas as P                          # noqa: E402
from paddle_tpu.ops.pallas import causal_conv1d as CK           # noqa: E402
from paddle_tpu.ops.ssm import _gated_conv                      # noqa: E402
from benchmark.reference import lfm2_moe as R                   # noqa: E402

HYPER = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8,
             weight_decay=0.1)
F32, BF16 = jnp.float32, jnp.bfloat16


def _plain(spec, a, b):
    return jnp.einsum(spec, a, b)


def _model(seed=5, **kw):
    """(model holding the reference's seeded weights, cfg dict, weights)."""
    config = Lfm2MoeConfig.tiny(**kw)
    cfg = dict(vars(config), first_layer=0)     # layer_types is cut already
    model = Lfm2MoeForCausalLM(config)
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
    got, ref = (np.asarray(t, np.float32) for t in (got, ref))
    return np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        recompute):
    """Five layers as the configuration cuts them: a conv layer over the
    dense feed-forward, an attention layer and three conv layers over
    experts; two sequences."""
    model, cfg, weights = _model(recompute=recompute)
    assert R.layer_kinds(cfg) == [
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False)]
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
    # embedding, 4 x 3 of the conv operators, 4 of attention, 3 of the
    # dense layer, 4 x 4 of the expert layers
    assert len(R.compared_leaves(cfg)) == 1 + 12 + 4 + 3 + 16
    for name, p in model.named_parameters():
        assert _rel(p._grad, want_grad[name]) < 2e-5, name


def test_parameters_after_one_adamw_step_are_the_references():
    """float32 through ``jit.to_static``: the loss, and every parameter's
    change after one AdamW step, leaf by leaf."""
    model, cfg, weights = _model(recompute=True)
    o = opt.AdamW(parameters=model.parameters(), **HYPER)

    def step(ids):
        loss = model.loss(model(ids), ids)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    batch = (_ids(seed=3),)
    got = float(jit.to_static(step, models=[model], optimizers=[o])(
        pt.to_tensor(batch[0])).numpy())
    want = R.train(cfg, HYPER, 5, [batch])
    assert abs(got - want["loss"][0]) < 1e-5
    for name, p in model.named_parameters():
        moved = float(jnp.sqrt(jnp.sum(jnp.square(p.data - weights[name]))))
        assert abs(moved - want["delta_norm"][name]) \
            <= 1e-4 * want["delta_norm"][name] + 1e-9, name


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


def test_the_tied_leafs_gradient_is_the_lookups_plus_the_heads():
    """One leaf, read twice: its gradient is the sum of what the look-up
    alone and the head alone would give it (the other use held
    constant)."""
    model, cfg, weights = _model()
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
    d_look_up, d_head = jax.grad(loss, (0, 1))(e, e)
    assert float(jnp.abs(d_look_up).max()) > 0 \
        and float(jnp.abs(d_head).max()) > 0
    assert _rel(got, d_look_up + d_head) < 2e-5
    assert _rel(got, d_head) > 1e-2 and _rel(got, d_look_up) > 1e-2


def test_config_reads_the_list_from_first_layer_on_and_the_share():
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
    model, _, _ = _model()
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


# -- the gated short convolution: one op, two routes --------------------------

@pytest.fixture()
def kernels_forced():
    P.configure(gated_short_conv=True)
    try:
        yield
    finally:
        P.configure(gated_short_conv=None)


def _traced():
    seen = monitor.snapshot("gated_short_conv")
    return (int(seen.get("gated_short_conv.kernel_traced", 0)),
            int(seen.get("gated_short_conv.xla_traced", 0)))


def _conv_inputs(batch, seq, channels, taps, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    tap = 1.0 / np.sqrt(taps)
    b, c, u = (jax.random.normal(k[i], (batch, seq, channels)).astype(dtype)
               for i in range(3))
    w = jax.random.uniform(k[3], (channels, taps), F32, -tap, tap)
    return [b, c, u, w], jax.random.normal(k[4], (batch, seq, channels))


def _composition(b, c, u, w):
    """What the op fuses, from the ops the repo had."""
    return c * F.causal_conv1d(b * u, w)


def _op(b, c, u, w):
    from paddle_tpu.ops import manip
    return F.gated_short_conv(manip.concat([b, c, u], axis=-1), w)


def _through_the_tape(fn, arrays, probe):
    leaves = [pt.Tensor(a, stop_gradient=False) for a in arrays]
    out = fn(*leaves)
    (out.astype("float32") * pt.Tensor(probe.astype(F32))).sum().backward()
    return out, [t._grad for t in leaves]


# rows: 256 is two tiles of 128 with B = 2, 384 three; 1024 two of 512;
# channels: 384 is three lane tiles of 128, so a third starts at lane 384
CONV_CASES = [(2, 256, 128, 3), (2, 384, 384, 3), (1, 1024, 128, 3),
              (2, 256, 256, 4), (1, 128, 128, 2)]


@pytest.mark.parametrize("route", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("batch,seq,channels,taps", CONV_CASES)
def test_gated_short_conv_is_the_composition_forward_and_four_gradients(
        request, route, dtype, batch, seq, channels, taps):
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    arrays, probe = _conv_inputs(batch, seq, channels, taps, dtype)
    assert CK.gated_supported((batch, seq, 3 * channels), taps)
    before = _traced()
    out, grads = _through_the_tape(_op, arrays, probe)
    after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) \
        == ((1, 0) if route == "kernels" else (0, 1))
    assert out.dtype == dtype and tuple(out.shape) == (batch, seq, channels)
    # the oracle: the composition on float32 copies of the same values
    f32 = [a.astype(F32) for a in arrays]
    want, want_grads = _through_the_tape(_composition, f32, probe)
    names = ("y", "b", "c", "u", "weight")
    got_all, want_all = [out.data] + grads, [want.data] + want_grads
    if dtype == F32:
        for name, g, w in zip(names, got_all, want_all):
            assert _rel(g, w) < 2e-6, name
        return
    # bfloat16: one rounding on the way out (half a unit in the last of
    # eight places, of the largest value at the most) where the composition
    # rounds at every stage: as near the oracle as that, or as the
    # composition at the call's own dtype is, and no further
    port, port_grads = _through_the_tape(_composition, arrays, probe)
    for name, g, w, p in zip(names, got_all, want_all,
                             [port.data] + port_grads):
        assert g.dtype == p.dtype, name
        assert _rel(g, w) <= max(1.1 * _rel(p, w), 2.0 ** -8) + 1e-4, name


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_a_sequence_starts_from_zeros_whatever_stands_before_it(request,
                                                                route):
    """B = 2, two row tiles of 128 a sequence: row 0 of sequence 1 (and
    every row of it) is unchanged when sequence 0 changes, forward and
    backward; inside a sequence a row tile's first rows read the tile
    before."""
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    (b, c, u, w), probe = _conv_inputs(2, 256, 128, 3, F32, seed=7)
    bcx = jnp.concatenate([b, c, u], -1)

    def run(bcx):
        t = pt.Tensor(bcx, stop_gradient=False)
        y = F.gated_short_conv(t, pt.Tensor(w))
        (y * pt.Tensor(probe)).sum().backward()
        return np.asarray(y.data), np.asarray(t._grad)

    y, g = run(bcx)
    other = bcx.at[0].set(jax.random.normal(jax.random.key(9), bcx.shape[1:]))
    y2, g2 = run(other)
    np.testing.assert_array_equal(y[1], y2[1])
    np.testing.assert_array_equal(g[1], g2[1])
    assert np.abs(y[0] - y2[0]).max() > 0.1
    # row 0 of a sequence sees zeros before it: y[0] = c[0] k_{K-1} (b u)[0]
    np.testing.assert_allclose(
        y[1, 0], np.asarray(c[1, 0] * w[:, 2] * b[1, 0] * u[1, 0]),
        rtol=1e-6, atol=1e-7)
    # the tile boundary at row 128: moving row 127 moves rows 127..129
    moved = bcx.at[1, 127].add(1.0)
    delta = np.abs(run(moved)[0][1] - y[1]).max(-1)
    assert delta[:127].max() == 0 and delta[127:130].min() > 0 \
        and delta[130:].max() == 0
    # and a cotangent at row 128 reaches rows 126..128 of d(b) and d(u)
    spike = jnp.zeros_like(probe).at[1, 128].set(1.0)
    t = pt.Tensor(bcx, stop_gradient=False)
    (F.gated_short_conv(t, pt.Tensor(w)) * pt.Tensor(spike)).sum().backward()
    db = np.abs(np.asarray(t._grad)[1, :, :128]).max(-1)
    assert db[:126].max() == 0 and db[126:129].min() > 0 \
        and db[129:].max() == 0
    assert np.abs(np.asarray(t._grad)[0]).max() == 0


def test_the_kernel_route_is_two_kernels_and_no_copy_of_a_third():
    """Forward and backward are one ``pallas_call`` each, behind
    module-level jits; nothing of rows x channels is sliced out of ``bcx``
    or concatenated into its gradient, and nothing is float32 at a
    kernel's boundary but the taps' gradient."""
    bcx = jax.ShapeDtypeStruct((2, 256, 768), BF16)
    w = jax.ShapeDtypeStruct((256, 3), F32)
    dy = jax.ShapeDtypeStruct((2, 256, 256), BF16)

    def both(a, b, ct):
        y, vjp = jax.vjp(CK.gated_short_conv, a, b)
        return y, vjp(ct)

    text = str(jax.make_jaxpr(both)(bcx, w, dy))
    assert text.count("pallas_call") == 2
    assert "name=gated_conv_fwd" in text and "name=gated_conv_bwd" in text
    assert "concatenate" not in text and "slice" not in text.replace(
        "dynamic_slice", "")
    assert "f32[2,256,768]" not in text and "f32[2,256,256]" not in text
    assert "bf16[2,256,768]" in text and "f32[2,3,256]" in text


def test_which_calls_take_the_kernels_is_read_off_the_call(monkeypatch):
    assert P.enabled("gated_short_conv") is False         # a CPU
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    assert P.enabled("gated_short_conv") is True
    monkeypatch.undo()
    assert CK.gated_supported((2, 8192, 6144), 3)          # the cell's
    assert not CK.gated_supported((2, 8192, 6145), 3)
    assert not CK.gated_supported((2, 8192, 3 * 64), 3)    # no lane tile
    assert not CK.gated_supported((2, 100, 384), 3)        # no row tile
    assert not CK.gated_supported((8192, 6144), 3)
    P.configure(gated_short_conv=True)
    try:
        (b, c, u, w), _ = _conv_inputs(1, 100, 128, 3, F32)
        before = _traced()
        got = _op(*(pt.Tensor(a) for a in (b, c, u, w)))
        assert _traced() == (before[0], before[1] + 1)      # portable path
        np.testing.assert_allclose(
            got.numpy(), _gated_conv(jnp.concatenate([b, c, u], -1), w),
            atol=1e-6)
    finally:
        P.configure(gated_short_conv=None)
    with pytest.raises(ValueError, match="three times"):
        F.gated_short_conv(pt.Tensor(jnp.zeros((1, 128, 256))),
                           pt.Tensor(jnp.zeros((128, 3))))


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

def test_the_attention_layer_is_the_references(monkeypatch):
    model, cfg, weights = _model()
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
    mt = pt.to_tensor(np.asarray(m))
    want = whole(mt).numpy()
    w = {k: p.data for k, p in whole.named_parameters()}
    cfg = dict(num_experts=32, num_experts_published=32,
               num_experts_per_tok=4, routed_scaling_factor=1.0)
    flat = m.reshape(48, 64)
    ref_whole = R._moe(cfg, w, flat, _plain)
    np.testing.assert_allclose(want.reshape(48, 64), ref_whole, atol=2e-6)
    total, ref_total = 0.0, 0.0
    for first in range(0, 32, 8):
        share = nn.RoutedMoE(64, 32, 32, 4,
                             experts_held=range(first, first + 8), **kind)
        share.router.weight.set_value(w["router.weight"])
        held = {k: w[k][first:first + 8]
                for k in ("experts_gate", "experts_up", "experts_down")}
        for k, v in held.items():
            getattr(share, k).set_value(v)
        part = share(mt).numpy()
        assert np.abs(part).max() > 0
        total = total + part
        ref_total = ref_total + R._moe(
            dict(cfg, num_experts=8, first_expert_held=first),
            dict(held, **{"router.weight": w["router.weight"]}), flat,
            _plain)
    np.testing.assert_allclose(total, want, atol=3e-6)
    np.testing.assert_allclose(ref_total, ref_whole, atol=3e-6)


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

def test_the_expert_layers_pad_on_the_ops_own_ladder(monkeypatch):
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
    model, _, _ = _model()
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
