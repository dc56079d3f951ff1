"""SmallThinker on the training path, at a tiny size on the CPU, against the
benchmark's plain float32 reference (benchmark/reference/smallthinker.py,
which imports nothing of paddle_tpu): window layers with rotary positions
and global layers without positions in one stack, the sliding window as a
fact of the flash call, the router that reads the layer's input, the
ReLU-gated experts and the chip's share of them, and the model trained
through ``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` +
``loss.backward()``. The contract with the reference is
tests/family_contract.py's; the windowed flash kernels themselves are in
tests/test_flash_window.py.
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
from benchmark.reference import smallthinker as R               # noqa: E402
from family_contract import (Family, Reference,                 # noqa: E402
                             check_expert_shares_add_up,
                             check_matches_reference,
                             check_trains_through_to_static, ids as _ids,
                             plain as _plain, routed_share)

FAMILY = Family(R, SmallThinkerForCausalLM, SmallThinkerConfig.tiny)


@pytest.fixture(scope="module")
def reference():
    return Reference(FAMILY)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        reference, recompute):
    """24 positions under a window of 8: three of the four layers are
    windowed and rotated, layer 0 global and position-free."""
    seen = check_matches_reference(reference, recompute)
    cfg = seen.cfg
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == (0, 1, 1, 1)
    assert tuple(seen.outputs[0].shape) == (2, 24, 256)
    assert len(R.compared_leaves(cfg)) == 2 + 4 * 8


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference(
        reference):
    # bf16 products against float32: the losses agree to bf16's rounding
    check_trains_through_to_static(reference, rtol=3e-3)


def test_config_reads_a_period_the_share_and_the_published_lists(reference):
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
    model, _, _ = reference.model()
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

def test_the_router_reads_the_layers_input(reference):
    """The chosen experts are those of the reference's router on the
    block's input; the scale of the norm in front of the experts moves the
    experts' input and not the choice."""
    model, cfg, weights = reference.model()
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
    w = {k: p.data for k, p in whole.named_parameters()}

    def layer(first, n):
        return whole if n == 64 else routed_share(
            lambda held: nn.RoutedMoE(64, 32, 64, 6, experts_held=held,
                                      **kind), w, first, n)

    check_expert_shares_add_up(
        R, w, layer, lambda first, n: dict(
            moe_num_primary_experts=n, moe_num_primary_experts_published=64,
            moe_num_active_primary_experts=6, first_expert_held=first),
        m, router_input=x, experts=64, held=8)


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
