"""``nn.RoutedMoE``, the dropless routed-experts layer and a chip's share
of it, on the CPU against the benchmark's plain reference
(benchmark/reference/nemotron_h.py: sigmoid router with a selection bias,
relu-squared experts, a shared expert): the shares add up to the uncut
layer, no slot is dropped, thin and crowded routing forward and gradient on
one rung and on the ladder of capacities, the counters the layer keeps on
the device (``monitor.device_counters``), and the buffers a recomputed
block hands back. No model is built here (tests/test_nemotron_h.py has the
model; the kernels under ``F.moe_experts`` are in
tests/test_moe_row_movement.py and tests/test_moe_grouped.py)."""
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
from paddle_tpu import jit, monitor, nn                     # noqa: E402
from paddle_tpu.models.nemotron_h import NemotronHConfig    # noqa: E402
from paddle_tpu.nn import functional as F                   # noqa: E402
from paddle_tpu.ops import moe as moe_ops                   # noqa: E402
from benchmark.reference import nemotron_h as R             # noqa: E402
from family_contract import (check_expert_shares_add_up,    # noqa: E402
                             plain as _plain)


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
    # each share against the reference given the same share, and their sum
    routed = check_expert_shares_add_up(
        R, weights, lambda first, n: _moe_layer(cfg, weights, first, n),
        lambda first, n: dict(cfg, n_routed_experts=n,
                              first_expert_held=first),
        u, experts=16, held=4, part_atol=2e-4, sum_atol=5e-4,
        stacked=("experts_up", "experts_down"),
        shared=lambda layer, t: layer.shared_down(
            F.relu(layer.shared_up(t)) ** 2))
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
