"""NemotronH on the training path, at a tiny size on the CPU, against the
benchmark's plain float32 reference (benchmark/reference/nemotron_h.py,
which imports nothing of paddle_tpu): the Mamba-2 scan, the dropless
routed experts and the chip's share of them, grouped-query attention
through the flash kernels, RMS norm, and the model trained through
``jit.to_static`` + ``amp.auto_cast`` + ``AdamW`` + ``loss.backward()``.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                     # noqa: E402
from paddle_tpu import amp, jit, monitor, nn                # noqa: E402
from paddle_tpu import optimizer as opt                     # noqa: E402
from paddle_tpu.models.nemotron_h import (NemotronHConfig,  # noqa: E402
                                          NemotronHForCausalLM)
from paddle_tpu.nn import functional as F                   # noqa: E402
from paddle_tpu.ops import moe as moe_ops                   # noqa: E402
from paddle_tpu.ops import ssm as ssm_ops                   # noqa: E402
from benchmark.reference import nemotron_h as R             # noqa: E402

HYPER = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8,
             weight_decay=0.1)


def _plain(spec, a, b):
    return jnp.einsum(spec, a, b)


def _model(seed=5, **kw):
    """(model holding the reference's seeded weights, cfg dict, weights)."""
    config = NemotronHConfig.tiny(**kw)
    cfg = dict(vars(config))
    model = NemotronHForCausalLM(config)
    weights = R.init_weights(cfg, seed)
    params = dict(model.named_parameters())
    assert set(params) == set(weights)
    for name, p in params.items():
        assert tuple(p.shape) == tuple(weights[name].shape), name
        p.set_value(weights[name])
    return model, cfg, weights


def _ids(rows=2, seq=21, vocab=256, seed=0):
    return np.random.default_rng(seed).integers(
        0, vocab, (rows, seq)).astype(np.int32)


# -- the model against the reference ---------------------------------------

@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_matches_the_reference_on_logits_loss_and_every_gradient(
        recompute):
    model, cfg, weights = _model(recompute=recompute)
    ids = _ids()
    logits = model(pt.to_tensor(ids))
    want = R.forward(cfg, weights, jnp.asarray(ids))
    np.testing.assert_allclose(logits.numpy(), want, atol=2e-6)
    loss = model.loss(logits, pt.to_tensor(ids))
    loss.backward()
    want_loss, want_grad = jax.value_and_grad(
        lambda q: R.loss_fn(cfg, q, (jnp.asarray(ids),)))(weights)
    assert abs(float(loss.numpy()) - float(want_loss)) < 1e-5
    compared = R.compared_leaves(cfg)
    assert len(compared) >= 16
    for name, p in model.named_parameters():
        got, ref = np.asarray(p._grad), np.asarray(want_grad[name])
        scale = np.abs(ref).max() + 1e-12
        assert np.abs(got - ref).max() / scale < 2e-5, name


def test_model_trains_through_to_static_amp_and_adamw_like_the_reference():
    model, cfg, _ = _model(recompute=True)
    o = opt.AdamW(parameters=model.parameters(),
                  **{k: v for k, v in HYPER.items()})

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
    np.testing.assert_allclose(got, want, rtol=2e-3)
    assert got[2] < got[0]


def test_pattern_and_config_are_checked():
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig.tiny(hybrid_override_pattern="MEX*")
    with pytest.raises(ValueError, match="hybrid_override_pattern"):
        NemotronHConfig.tiny(num_hidden_layers=5)
    assert len(NemotronHConfig().hybrid_override_pattern) == 52


# -- the state-space scan ---------------------------------------------------

def _scan_inputs(seq, heads=4, width=8, groups=2, state=16, seed=1):
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (2, seq, heads, width)),
        dt=jax.random.normal(k[1], (2, seq, heads)),
        a_log=jnp.log(jax.random.uniform(k[2], (heads,), minval=1.0,
                                         maxval=16.0)),
        b=jax.random.normal(k[3], (2, seq, groups, state)),
        c=jax.random.normal(k[4], (2, seq, groups, state)),
        d=jax.random.normal(k[5], (heads,)),
        dt_bias=jax.random.normal(k[6], (heads,)) - 2.0)


def _step_by_step(x, dt, a_log, b, c, d, dt_bias):
    r = x.shape[2] // b.shape[2]
    one = lambda x, dt, b, c: R.ssm_step_by_step(       # noqa: E731
        x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
        jnp.repeat(b, r, 1), jnp.repeat(c, r, 1), d)
    return jax.vmap(one)(x, dt, b, c)


# 16, 32: whole chunks; 21, 5: a padded tail, and fewer positions than one
@pytest.mark.parametrize("seq", [16, 32, 21, 5])
def test_chunked_scan_equals_the_recurrence_forward_and_gradient(seq):
    t = _scan_inputs(seq)
    order = ("x", "dt", "a_log", "b", "c", "d", "dt_bias")
    tensors = {k: pt.to_tensor(np.asarray(v)) for k, v in t.items()}
    for v in tensors.values():
        v.stop_gradient = False
    y = F.ssd_scan(tensors["x"], tensors["dt"], tensors["a_log"],
                   tensors["b"], tensors["c"], tensors["d"],
                   tensors["dt_bias"], chunk_size=8)
    want = _step_by_step(*(t[k] for k in order))
    np.testing.assert_allclose(y.numpy(), want, atol=2e-4, rtol=2e-4)

    probe = jax.random.normal(jax.random.key(9), want.shape)
    (y * pt.to_tensor(np.asarray(probe))).sum().backward()
    grads = jax.grad(lambda *a: jnp.sum(_step_by_step(*a) * probe),
                     argnums=tuple(range(7)))(*(t[k] for k in order))
    for name, g in zip(order, grads):
        got = np.asarray(tensors[name]._grad)
        scale = np.abs(np.asarray(g)).max() + 1e-12
        assert np.abs(got - np.asarray(g)).max() / scale < 5e-4, name


def test_scan_keeps_decays_in_float32_under_autocast():
    t = _scan_inputs(24)
    args = [pt.to_tensor(np.asarray(t[k]))
            for k in ("x", "dt", "a_log", "b", "c", "d", "dt_bias")]
    want = F.ssd_scan(*args, chunk_size=8).numpy()
    with amp.auto_cast(dtype="bfloat16"):
        got = F.ssd_scan(*args, chunk_size=8)
    assert got.dtype == jnp.float32          # x's dtype, not the products'
    assert np.abs(got.numpy() - want).max() < 0.05 * np.abs(want).max()


def test_causal_conv1d_is_causal_and_matches_a_plain_sum():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 9, 6)).astype(np.float32)
    w = rng.normal(size=(6, 4)).astype(np.float32)
    b = rng.normal(size=(6,)).astype(np.float32)
    y = F.causal_conv1d(pt.to_tensor(x), pt.to_tensor(w), pt.to_tensor(b),
                        activation="silu").numpy()
    pad = np.concatenate([np.zeros((2, 3, 6), np.float32), x], 1)
    want = sum(pad[:, j:j + 9] * w[:, j] for j in range(4)) + b
    want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(y, want, atol=1e-5)
    x2 = x.copy()
    x2[:, 5:] += 1.0                         # the future moves nothing past
    y2 = F.causal_conv1d(pt.to_tensor(x2), pt.to_tensor(w), pt.to_tensor(b),
                         activation="silu").numpy()
    np.testing.assert_array_equal(y[:, :5], y2[:, :5])
    with pytest.raises(ValueError, match="activation"):
        F.causal_conv1d(pt.to_tensor(x), pt.to_tensor(w), activation="gelu")


# -- RMS norm ---------------------------------------------------------------

@pytest.mark.parametrize("groups,gated", [(1, False), (4, False), (4, True)])
def test_rms_norm_matches_the_reference(groups, gated):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(3, 5, 32)).astype(np.float32)
    g = rng.normal(size=(3, 5, 32)).astype(np.float32)
    w = rng.normal(size=(32,)).astype(np.float32)
    layer = nn.RMSNorm(32, epsilon=1e-5, num_groups=groups)
    layer.weight.set_value(w)
    got = layer(pt.to_tensor(x), gate=pt.to_tensor(g) if gated else None)
    inp = x * np.asarray(jax.nn.silu(g)) if gated else x
    want = R._rms_norm(jnp.asarray(inp), jnp.asarray(w), 1e-5, groups)
    np.testing.assert_allclose(got.numpy(), want, atol=1e-5)
    with amp.auto_cast(dtype="bfloat16"):
        half = layer(pt.to_tensor(x).astype("bfloat16"))
    assert half.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="groups"):
        nn.RMSNorm(30, num_groups=4)


# -- the mixer's convolution and gated norm as kernels -----------------------

# the tiny widths padded to what the kernels' tiles take: 128 positions,
# heads of 64 in two groups (a group of the norm is 128 lanes), state 32
# (the convolution runs over 256 + 2 * 2 * 32 = 384 channels)
_KERNEL_WIDTHS = dict(mamba_head_dim=64, ssm_state_size=32, n_groups=2)


def _with_stage_kernels(forced, run):
    """``run()`` with the two kernel pairs forced (interpret mode) or
    left to the registry (off on a CPU), and how many call sites traced
    each: ((conv kernel, conv XLA), (norm kernel, norm XLA))."""
    from paddle_tpu.ops import pallas as P

    def traced():
        conv, norm = (monitor.snapshot(k) for k in ("causal_conv1d",
                                                     "rms_norm"))
        return np.array([
            [conv.get("causal_conv1d.kernel_traced", 0),
             conv.get("causal_conv1d.xla_traced", 0)],
            [norm.get("rms_norm.gated_kernel_traced", 0),
             norm.get("rms_norm.gated_xla_traced", 0)]], int)

    if forced:
        P.configure(causal_conv1d=True, gated_rms_norm=True)
    try:
        before = traced()
        out = run()
        return out, (traced() - before).tolist()
    finally:
        P.configure(causal_conv1d=None, gated_rms_norm=None)


def test_mixer_gives_the_same_result_and_gradients_through_the_kernels():
    pt.seed(3)
    mixer = nn.Mamba2Mixer(64, 4, 64, 32, n_groups=2, chunk_size=32)
    u = np.random.default_rng(1).normal(size=(2, 128, 64)).astype(np.float32)
    probe = np.random.default_rng(2).normal(size=(2, 128, 64)).astype(
        np.float32)

    def run():
        x = pt.to_tensor(u)
        x.stop_gradient = False
        for p in mixer.parameters():
            p._grad = None
        y = mixer(x)
        (y * pt.to_tensor(probe)).sum().backward()
        return [y.numpy(), np.asarray(x._grad)] + [
            np.asarray(p._grad) for _, p in mixer.named_parameters()]

    plain, took = _with_stage_kernels(False, run)
    assert took == [[0, 1], [0, 1]]
    kernels, took = _with_stage_kernels(True, run)
    assert took == [[1, 0], [1, 0]]
    names = ["y", "u"] + [n for n, _ in mixer.named_parameters()]
    assert len(plain) == len(names) == 10
    for name, a, b in zip(names, plain, kernels):
        assert np.abs(a - b).max() <= 2e-5 * np.abs(a).max(), name


@pytest.mark.parametrize("recompute", [False, True],
                         ids=["plain", "recompute"])
def test_model_gives_the_same_loss_and_gradients_through_the_kernels(
        recompute):
    """The whole model (``ME`` at the padded widths), every block
    recomputed or not: the loss and every parameter's gradient with the
    convolution and the gated norm through their kernels are the portable
    paths', and both are the reference's."""
    model, cfg, weights = _model(recompute=recompute, num_hidden_layers=2,
                                 hybrid_override_pattern="ME",
                                 **_KERNEL_WIDTHS)
    ids = _ids(rows=2, seq=128)

    def run():
        for p in model.parameters():
            p._grad = None
        loss = model.loss(model(pt.to_tensor(ids)), pt.to_tensor(ids))
        loss.backward()
        return float(loss.numpy()), {n: np.asarray(p._grad)
                                     for n, p in model.named_parameters()}

    (plain_loss, plain), took = _with_stage_kernels(False, run)
    assert took == [[0, 1], [0, 1]]         # one mixer, traced once
    (loss, grads), took = _with_stage_kernels(True, run)
    assert took == [[1, 0], [1, 0]]
    assert abs(loss - plain_loss) < 1e-6
    want_loss, want = jax.value_and_grad(
        lambda q: R.loss_fn(cfg, q, (jnp.asarray(ids),)))(weights)
    assert abs(loss - float(want_loss)) < 1e-5
    for name, g in grads.items():
        scale = np.abs(plain[name]).max() + 1e-12
        assert np.abs(g - plain[name]).max() / scale < 2e-5, name
        ref = np.asarray(want[name])
        assert np.abs(g - ref).max() / (np.abs(ref).max() + 1e-12) < 5e-5, \
            name


# -- routed experts ---------------------------------------------------------

def _moe_cfg(**kw):
    cfg = dict(vars(NemotronHConfig.tiny()))
    cfg.update(kw)
    return cfg


def _moe_layer(cfg, weights, first, held):
    layer = nn.RoutedMoE(
        cfg["hidden_size"], cfg["moe_intermediate_size"],
        cfg["n_routed_experts_published"], cfg["num_experts_per_tok"],
        d_shared=cfg["moe_shared_expert_intermediate_size"],
        experts_held=range(first, first + held),
        routed_scaling_factor=cfg["routed_scaling_factor"])
    layer.router.weight.set_value(weights["router.weight"])
    layer.experts_up.set_value(weights["experts_up"][first:first + held])
    layer.experts_down.set_value(weights["experts_down"][first:first + held])
    layer.shared_up.weight.set_value(weights["shared_up.weight"])
    layer.shared_down.weight.set_value(weights["shared_down.weight"])
    return layer


def _whole_layer_weights(cfg, seed=2):
    """An expert layer's weights with ALL the published experts."""
    d, e = cfg["hidden_size"], cfg["n_routed_experts_published"]
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    k = jax.random.split(jax.random.key(seed), 5)
    return {"router.weight": jax.random.normal(k[0], (d, e)),
            "experts_up": 0.2 * jax.random.normal(k[1], (e, d, f)),
            "experts_down": 0.2 * jax.random.normal(k[2], (e, f, d)),
            "shared_up.weight": 0.2 * jax.random.normal(k[3], (d, fs)),
            "shared_down.weight": 0.2 * jax.random.normal(k[4], (fs, d))}


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Guide ``model-configs`` section 4: the parts all 4 shares give (4 of
    16 experts each), the shared expert counted once, are the whole
    layer."""
    cfg = _moe_cfg()
    weights = _whole_layer_weights(cfg)
    u = jax.random.normal(jax.random.key(7), (2, 13, cfg["hidden_size"]))
    whole = dict(cfg, n_routed_experts=16, first_expert_held=0)
    want = R._moe(whole, weights, u.reshape(-1, 64), _plain).reshape(u.shape)

    shared = _moe_layer(cfg, weights, 0, 4)
    shared = shared.shared_down(
        F.relu(shared.shared_up(pt.to_tensor(np.asarray(u)))) ** 2).numpy()
    total = np.zeros_like(shared)
    routed = 0
    for first in (0, 4, 8, 12):
        layer = _moe_layer(cfg, weights, first, 4)
        total += layer(pt.to_tensor(np.asarray(u))).numpy() - shared
        # the reference given the same share agrees with the program's
        part = dict(cfg, n_routed_experts=4, first_expert_held=first)
        held = dict(weights,
                    experts_up=weights["experts_up"][first:first + 4],
                    experts_down=weights["experts_down"][first:first + 4])
        np.testing.assert_allclose(
            layer(pt.to_tensor(np.asarray(u))).numpy(),
            R._moe(part, held, u.reshape(-1, 64), _plain).reshape(u.shape),
            atol=2e-4)
        routed += int(layer.stats.numpy()[0]) // 2      # called twice
    np.testing.assert_allclose(total + shared, want, atol=5e-4)
    assert routed == 2 * 13 * cfg["num_experts_per_tok"]   # every slot, once


@pytest.mark.parametrize("min_rows,computed", [(512, 4 * 33),
                                               (4, 3 * 33 + 4)])
def test_no_slot_is_dropped_when_every_token_goes_to_the_held_experts(
        monkeypatch, min_rows, computed):
    """33 tokens: one rung of 33 rows an expert, or the ladder 4, 8, 16,
    32, 33, on which the three full experts take the last rung and the
    empty one the first."""
    monkeypatch.setattr(moe_ops, "MIN_ROWS", min_rows)
    cfg = _moe_cfg()
    weights = _whole_layer_weights(cfg)
    layer = _moe_layer(cfg, weights, 4, 4)
    bias = np.zeros(16, np.float32)
    bias[4:7] = 10.0      # the selection bias sends every token's 3 choices
    layer.e_score_correction_bias.set_value(bias)       # to experts 4, 5, 6
    monitor.device_counters.reset()
    monitor.device_counters.register(nn.RoutedMoE.COUNTERS, layer.stats,
                                     owner=layer)
    u = jax.random.normal(jax.random.key(8), (3, 11, cfg["hidden_size"]))
    tensor = pt.to_tensor(np.asarray(u))
    tensor.stop_gradient = False
    y = layer(tensor)
    seen = monitor.device_counters.read()
    assert seen == {"moe.slots_routed_here": 3 * 11 * 3,
                    "moe.slots_dropped": 0,
                    "moe.expert_load_max": 3 * 11, "moe.steps": 1,
                    "moe.rows_computed": computed}

    def plain(u, w):
        chosen, gates = R.route(cfg, u, w["router.weight"],
                                jnp.asarray(bias))
        assert set(np.unique(chosen)) == {4, 5, 6}
        out = R._relu2_mlp(u, w["shared_up.weight"], w["shared_down.weight"],
                           _plain)
        for e in (4, 5, 6, 7):
            gate = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
            out = out + gate[:, None] * R._relu2_mlp(
                u, w["experts_up"][e], w["experts_down"][e], _plain)
        return out

    want = plain(u.reshape(-1, 64), weights).reshape(u.shape)
    np.testing.assert_allclose(y.numpy(), want, atol=5e-4)
    # and the gradient: into the tokens, and into an expert that got rows
    (y * y).sum().backward()
    g_u, g_w = jax.grad(lambda u, w: jnp.sum(jnp.square(plain(u, w))),
                        argnums=(0, 1))(u.reshape(-1, 64), weights)
    np.testing.assert_allclose(np.asarray(tensor._grad).reshape(-1, 64), g_u,
                               atol=2e-2, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(layer.experts_up._grad),
                               g_w["experts_up"][4:8], atol=2e-2, rtol=2e-3)
    assert np.abs(np.asarray(layer.experts_up._grad)[3]).max() == 0.0
    monitor.device_counters.reset()


@pytest.mark.parametrize("min_rows", [512, 4])
@pytest.mark.parametrize("published,fullest_at_least", [(64, 1), (8, 12)])
def test_thin_and_crowded_routing_give_the_reference_forward_and_gradient(
        monkeypatch, published, fullest_at_least, min_rows):
    """40 tokens x top-3 over 64 experts leave the 4 held experts a few
    rows each; over 8 experts every held one is crowded. Forward, and the
    gradient into tokens, experts and router, against the reference —
    with every expert on one rung of 40 rows, and with each on the rung
    of the ladder 4, 8, 16, 32, 40 that holds its rows."""
    monkeypatch.setattr(moe_ops, "MIN_ROWS", min_rows)
    cfg = _moe_cfg(n_routed_experts_published=published)
    weights = _whole_layer_weights(cfg, seed=4)
    layer = _moe_layer(cfg, weights, 2, 4)
    u = jax.random.normal(jax.random.key(9), (2, 20, cfg["hidden_size"]))
    tensor = pt.to_tensor(np.asarray(u))
    tensor.stop_gradient = False
    y = layer(tensor)
    routed, dropped, fullest, calls, computed = layer.stats.numpy()
    assert dropped == 0 and calls == 1
    assert fullest_at_least <= fullest <= 40 and fullest <= routed <= 120
    if min_rows == 512:
        assert computed == 4 * 40
    else:       # under half of a rung is padding, or it is the first rung
        assert routed <= computed <= min(2 * routed + 4 * 4, 4 * 40)

    part = dict(cfg, n_routed_experts=4, first_expert_held=2)

    def plain(u, w):
        held = dict(w, experts_up=w["experts_up"][2:6],
                    experts_down=w["experts_down"][2:6])
        return R._moe(part, held, u, _plain)

    flat = u.reshape(-1, 64)
    np.testing.assert_allclose(y.numpy().reshape(-1, 64),
                               plain(flat, weights), atol=5e-4)
    (y * y).sum().backward()
    g_u, g_w = jax.grad(lambda u, w: jnp.sum(jnp.square(plain(u, w))),
                        argnums=(0, 1))(flat, weights)
    np.testing.assert_allclose(np.asarray(tensor._grad).reshape(-1, 64), g_u,
                               atol=2e-2, rtol=2e-3)
    for name, got in (("experts_up", layer.experts_up),
                      ("experts_down", layer.experts_down)):
        np.testing.assert_allclose(np.asarray(got._grad), g_w[name][2:6],
                                   atol=2e-2, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(layer.router.weight._grad),
                               g_w["router.weight"], atol=2e-2, rtol=2e-3)


def test_router_ranks_with_the_bias_and_weighs_without_it():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(7, 8)).astype(np.float32)
    w = rng.normal(size=(8, 6)).astype(np.float32)
    bias = np.array([5.0, 0, 0, 0, 0, 5.0], np.float32)
    gates, chosen = F.moe_route(pt.to_tensor(x), pt.to_tensor(w),
                                pt.to_tensor(bias), top_k=2, scale=2.5)
    assert set(np.unique(chosen.numpy())) == {0, 5}
    s = 1.0 / (1.0 + np.exp(-(x @ w)))
    picked = np.take_along_axis(s, chosen.numpy(), -1)
    np.testing.assert_allclose(
        gates.numpy(), 2.5 * picked / picked.sum(-1, keepdims=True),
        rtol=1e-5)
    np.testing.assert_allclose(gates.numpy().sum(-1), 2.5, rtol=1e-5)


def test_experts_held_has_to_be_a_range_of_the_experts():
    with pytest.raises(ValueError, match="experts_held"):
        nn.RoutedMoE(8, 4, 16, 2, experts_held=range(12, 20))
    with pytest.raises(ValueError, match="experts_held"):
        nn.RoutedMoE(8, 4, 16, 2, experts_held=range(0, 8, 2))
    whole = nn.RoutedMoE(8, 4, 16, 2)
    assert tuple(whole.experts_up.shape) == (16, 8, 4)
    assert moe_ops.MOE_STATS[1] == "slots_dropped"


# -- grouped-query attention ------------------------------------------------

def test_gqa_through_the_flash_kernels_at_head_size_128_causal():
    layer = nn.GroupedQueryAttention(64, 4, 2, 128, causal=True)
    x = pt.to_tensor(np.asarray(
        jax.random.normal(jax.random.key(4), (1, 40, 64))))
    got = layer(x, force_flash=True)            # the kernels, interpreted
    q, k, v = (layer._heads(proj(x), 1, 40, count) for proj, count in (
        (layer.q_proj, 4), (layer.k_proj, 2), (layer.v_proj, 2)))
    plain = F.scaled_dot_product_attention(q, k, v, is_causal=True)
    plain = layer.o_proj(plain.transpose([0, 2, 1, 3]).reshape([1, 40, 512]))
    np.testing.assert_allclose(got.numpy(), plain.numpy(), atol=2e-5)
    # off a TPU, and unforced, the dispatch takes that same path
    np.testing.assert_allclose(layer(x).numpy(), plain.numpy(), atol=1e-6)
    # and both are the reference's attention
    w = {k + ".weight": getattr(layer, k).weight.data
         for k in ("q_proj", "k_proj", "v_proj", "o_proj")}
    cfg = dict(num_attention_heads=4, num_key_value_heads=2, head_dim=128)
    want = R._attention(cfg, w, x.data[0], _plain)
    np.testing.assert_allclose(got.numpy()[0], want, atol=2e-5)
    with pytest.raises(ValueError, match="key/value"):
        nn.GroupedQueryAttention(64, 4, 3, 16)


# -- counters and recomputation --------------------------------------------

def test_device_counters_add_up_sources_and_outlive_their_layers():
    monitor.device_counters.reset()
    a = pt.to_tensor(np.array([1, 2], np.int32))
    b = pt.to_tensor(np.array([10, 20], np.int32))
    monitor.device_counters.register(("t.x", "t.y"), a)
    monitor.device_counters.register(("t.x", "t.y"), b)
    a.data = a.data + 5                       # what a compiled step does
    assert monitor.device_counters.read() == {"t.x": 16, "t.y": 27}
    assert monitor.device_counters.read("t.y") == {"t.y": 27}
    with pytest.raises(ValueError, match="count"):
        monitor.device_counters.register(("one",), a)
    monitor.device_counters.reset()
    assert monitor.device_counters.read() == {}


def test_device_counters_fold_a_freed_owner_and_count_past_32_bits():
    monitor.device_counters.reset()

    class Owner:
        pass

    owner, kept = Owner(), Owner()
    a = pt.to_tensor(np.zeros(1, np.int32))
    b = pt.to_tensor(np.zeros(1, np.int32))
    monitor.device_counters.register(("t.n",), a, owner=owner)
    monitor.device_counters.register(("t.n",), b, owner=kept)
    from paddle_tpu.monitor.device_counters import _sources
    # int32 on the device wraps; the reads add up differences modulo 2**32
    a.data = a.data + (2**31 - 1)
    assert monitor.device_counters.read() == {"t.n": 2**31 - 1}
    a.data = a.data + (2**31 - 1)            # wrapped: reads as -2
    a.data = a.data + 7
    b.data = b.data + 1
    del owner                  # the model is freed before the last read
    assert monitor.device_counters.read() == {"t.n": 2**32 + 6}
    assert len(_sources) == 1                # a's array was let go
    b.data = b.data + 1
    assert monitor.device_counters.read() == {"t.n": 2**32 + 7}
    monitor.device_counters.reset()


def test_the_ladder_of_capacities_ends_at_the_tokens():
    assert moe_ops._ladder(8192, 512) == (512, 1024, 2048, 4096, 8192)
    assert moe_ops._ladder(40, 4) == (4, 8, 16, 32, 40)
    assert moe_ops._ladder(33, 512) == (33,)
    assert moe_ops._ladder(512, 512) == (512,)


def test_a_recomputed_block_hands_the_buffers_it_wrote_back():
    """``jit.recompute`` restores every holder when its body ends; a buffer
    the body wrote (the experts' counters) leaves as an explicit output."""
    cfg = _moe_cfg()
    layer = _moe_layer(cfg, _whole_layer_weights(cfg), 0, 4)
    u = pt.to_tensor(np.asarray(
        jax.random.normal(jax.random.key(1), (2, 9, cfg["hidden_size"]))))
    u.stop_gradient = False
    plain = layer(u).numpy()
    once = layer.stats.numpy().copy()
    assert once[3] == 1
    y = jit.recompute(layer, u)
    np.testing.assert_allclose(y.numpy(), plain, atol=1e-6)
    np.testing.assert_array_equal(layer.stats.numpy(), 2 * once)
    y.sum().backward()                        # the replay counts nothing
    np.testing.assert_array_equal(layer.stats.numpy(), 2 * once)
    assert layer.experts_up._grad is not None



def test_a_recomputed_batch_norm_hands_its_running_statistics_back():
    """The same path with no expert in it: a batch norm's running mean and
    variance, written inside ``jit.recompute``'s body, are what the plain
    call leaves, and the replay in the backward pass moves them no
    further."""
    x = pt.to_tensor(np.asarray(
        jax.random.normal(jax.random.key(2), (6, 3, 4, 4))) * 2.0 + 1.0)
    x.stop_gradient = False
    plain, again = nn.BatchNorm2D(3), nn.BatchNorm2D(3)
    want = plain(x).numpy()
    got = jit.recompute(again, x)
    np.testing.assert_allclose(got.numpy(), want, atol=1e-6)
    for name in ("_mean", "_variance"):
        moved = getattr(again, name).numpy()
        np.testing.assert_allclose(moved, getattr(plain, name).numpy(),
                                   atol=1e-6)
        assert np.abs(moved - (name == "_variance")).max() > 1e-3
    got.sum().backward()
    np.testing.assert_allclose(again._mean.numpy(), plain._mean.numpy(),
                               atol=1e-6)
