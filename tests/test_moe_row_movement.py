"""The expert layers' row movement (PERF.md section 6, PR 38), tiny on the
CPU: the router's pick as a select over the expert axis against
``take_along_axis``; the experts' combine as the in-place row scatter-add
kernel (interpret mode) against ``.at[].add``, alone at every rung of a
ladder and inside ``F.moe_experts``, forward and gradients; the counters
that say which path a call site traced; and the guard on the set-up: the
kernel is lowered once a module for a rung, however many layers and call
sites the step has.
"""
import functools
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                         # noqa: E402
from paddle_tpu import jit, monitor, nn                         # noqa: E402
from paddle_tpu import optimizer as opt                         # noqa: E402
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.ops import moe as moe_ops                       # noqa: E402
from paddle_tpu.ops import pallas as P                          # noqa: E402
from paddle_tpu.ops.pallas import moe_scatter_add_mod as K      # noqa: E402


@pytest.fixture()
def kernel_on():
    """The registry's switch, as the other kernels' CPU tests take it: on
    a CPU the kernel then runs in interpret mode."""
    P.configure(moe_scatter_add=True)
    yield
    P.configure(moe_scatter_add=None)


# -- the router's pick ------------------------------------------------------

def _route_with_gather(x, w, b, top_k, scale, scoring):
    """``F.moe_route`` as it was: the pick by ``take_along_axis``."""
    score = jax.nn.sigmoid if scoring == "sigmoid" else jax.nn.softmax
    s = score(jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                         w.astype(jnp.float32), precision="highest"))
    ranked = s if b is None else s + jax.lax.stop_gradient(b)
    _, experts = jax.lax.top_k(ranked, top_k)
    picked = jnp.take_along_axis(s, experts, -1)
    return scale * picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20), \
        experts.astype(jnp.int32)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("scoring,biased", [("sigmoid", True),
                                            ("sigmoid", False),
                                            ("softmax", False)])
def test_the_select_gives_the_gathers_values_and_gradients(scoring, biased,
                                                           dtype):
    key = jax.random.key(7)
    x = jax.random.normal(key, (2, 24, 32)).astype(dtype)
    w = 0.5 * jax.random.normal(jax.random.fold_in(key, 1), (32, 16))
    b = 0.3 * jax.random.normal(jax.random.fold_in(key, 2), (16,)) \
        if biased else None
    mix = jax.random.normal(jax.random.fold_in(key, 3), (2, 24, 3))

    def now(x, w):
        weights, experts = F.moe_route(
            pt.to_tensor(x), pt.to_tensor(w),
            None if b is None else pt.to_tensor(b), top_k=3, scale=2.5,
            scoring=scoring)
        return weights.data, experts.data

    def before(x, w):
        return _route_with_gather(x, w, b, 3, 2.5, scoring)

    got, want = now(x, w), before(x, w)
    assert got[0].dtype == jnp.float32 and got[1].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    # the sum over the expert axis holds one term that is not zero
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
    grads = [jax.grad(lambda x, w: jnp.sum(f(x, w)[0] * mix),
                      argnums=(0, 1))(x, w) for f in (now, before)]
    for g, h in zip(*grads):
        assert g.dtype == h.dtype
        np.testing.assert_allclose(np.asarray(g, np.float32),
                                   np.asarray(h, np.float32),
                                   rtol=2e-6 if dtype == jnp.float32
                                   else 1e-2, atol=1e-7)


def test_the_routers_backward_holds_no_scatter():
    """What the select is for: the gather's gradient scatters one element
    a chosen expert into ``[tokens, experts]``; the select's is a
    broadcast and a sum."""
    x = jnp.ones((1, 8, 16))
    w = jnp.ones((16, 8))

    def loss(x, w):
        weights, _ = F.moe_route(pt.to_tensor(x), pt.to_tensor(w), top_k=2)
        return jnp.sum(jnp.square(weights.data))

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w))
    assert "scatter" not in text and "gather" not in text
    assert "select_n" in text


# -- the kernel alone -------------------------------------------------------

LADDER = moe_ops._ladder(80, 8)     # 8, 16, 32, 64 and the 80 tokens


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("cap", LADDER)
def test_scatter_add_kernel_is_the_indexed_add_at_every_rung(cap, dtype):
    assert LADDER == (8, 16, 32, 64, 80)
    key = jax.random.key(cap)
    acc = jax.random.normal(key, (80, 1, 256), jnp.float32)
    rows = jax.random.normal(jax.random.fold_in(key, 1),
                             (cap, 256)).astype(dtype)
    at = jax.random.permutation(jax.random.fold_in(key, 2),
                                80)[:cap].astype(jnp.int32)
    want = acc.reshape(80, 256).at[at].add(rows.astype(jnp.float32),
                                           unique_indices=True)
    got = K.scatter_add(acc, at, rows, interpret=True)
    assert got.shape == (80, 1, 256) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got).reshape(80, 256),
                                  np.asarray(want))


def test_the_kernels_tiles():
    assert K.row_tile(1024, 2048) == 256 and K.row_tile(512, 2688) == 128
    assert K.row_tile(16384, 2048) == 256 and K.row_tile(80, 256) == 16
    assert K.row_tile(33, 128) == 33          # a small capacity, whole
    assert K.row_tile(8193, 2048) is None
    assert K.supported(2688, moe_ops._ladder(8192, 512))
    assert K.supported(2048, moe_ops._ladder(16384, 512))
    assert not K.supported(2048 + 64, (512,))  # no whole 128-lane tile
    assert not K.supported(2048, (512, 8193))


# -- inside F.moe_experts ---------------------------------------------------

def _experts_inputs(gated, dtype, crowded, tokens=80, d=128, f=32, held=4,
                    width=8, k=2):
    key = jax.random.key(3)
    x = jax.random.normal(key, (1, tokens, d)).astype(dtype)
    if crowded:     # every token chooses experts 0 and 1: the last rung
        experts = jnp.tile(jnp.arange(k, dtype=jnp.int32), (1, tokens, 1))
    else:           # a few rows an expert: capacity above its own rows
        _, experts = jax.lax.top_k(jax.random.uniform(
            jax.random.fold_in(key, 1), (1, tokens, width)), k)
    weights = jax.random.uniform(jax.random.fold_in(key, 2),
                                 (1, tokens, k), jnp.float32)
    ws = [0.3 * jax.random.normal(jax.random.fold_in(key, 3 + i), shape)
          for i, shape in enumerate([(held, d, f), (held, f, d)]
                                    + [(held, d, f)] * gated)]
    return (x, experts.astype(jnp.int32), weights, *ws)


@functools.lru_cache(maxsize=None)
def _routed_step(gated, dtype, kernel):
    """Loss, ``(y, stats)`` and every gradient of ``_routed`` as one jitted
    program, traced at its first call (under the caller's ``MIN_ROWS``)."""
    def loss(x, e, w, *ws):
        y, stats = moe_ops._routed(x, e, w, *ws, first=0, dot_dtype=dtype,
                                   kernel=kernel)
        return jnp.sum(jnp.square(y.astype(jnp.float32))), (y, stats)
    return jax.jit(jax.value_and_grad(
        loss, argnums=(0,) + tuple(range(2, 5 + gated)), has_aux=True))


@pytest.mark.parametrize("crowded", [False, True], ids=["thin", "crowded"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_experts_through_the_kernel_equal_the_indexed_add(monkeypatch, gated,
                                                          dtype, crowded):
    """Forward, ``dx`` and every other gradient, to the bit: the kernel
    adds the same float32 rows in the scan's order."""
    monkeypatch.setattr(moe_ops, "MIN_ROWS", 8)
    a = _experts_inputs(gated, dtype, crowded)

    def run(kernel):
        # thin and crowded differ in what the router chose, not in the
        # program: the two of a (gated, dtype) share one compiled step
        return _routed_step(gated, dtype, kernel)(*a)

    ((_, (y, stats)), grads), ((_, (y0, stats0)), grads0) = run(True), \
        run(False)
    rows = int(stats[4])
    # thin: four experts draw ~20 rows each and compute the 32 rung;
    # crowded: two draw all 80 tokens, two none (the first rung)
    assert rows == (2 * 80 + 2 * 8 if crowded else rows) and rows >= 80
    np.testing.assert_array_equal(np.asarray(stats), np.asarray(stats0))
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y0, np.float32))
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32)))) > 0
    for g, g0 in zip(grads, grads0):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(g0, np.float32))


def test_counters_say_which_path_a_call_site_traced(kernel_on):
    monitor.enable()
    reg = monitor.registry()

    def counts():
        return (int(reg.value("moe_experts.kernel_traced", 0)),
                int(reg.value("moe_experts.xla_traced", 0)))

    def call(d):
        a = _experts_inputs(False, jnp.float32, False, tokens=16, d=d, f=8)
        return F.moe_experts(*(pt.to_tensor(t) for t in a[:5]))

    k0, x0 = counts()
    y, _ = call(128)                      # whole lane tiles: the kernel
    assert counts() == (k0 + 1, x0)
    call(96)                              # no whole tile: XLA's scatter
    assert counts() == (k0 + 1, x0 + 1)
    P.configure(moe_scatter_add=None)     # auto: a CPU has no kernel path
    y0, _ = call(128)
    assert counts() == (k0 + 1, x0 + 2)
    np.testing.assert_array_equal(y.numpy(), y0.numpy())


# -- the guard on the set-up ------------------------------------------------

class _Stack(nn.Layer):
    def __init__(self, layers, d, wide=32):
        super().__init__()
        self.blocks = nn.LayerList([
            nn.RoutedMoE(d, wide, 8, 2, experts_held=range(4), gated=True,
                         scoring="softmax") for _ in range(layers)])

    def forward(self, u):
        for block in self.blocks:
            u = u + jit.recompute(block, u)
        return u


_MAKE_ENTRY = jit.StaticFunction._make_entry


def _lowered_step(monkeypatch, layers, d=128, grouped=False):
    """The StableHLO of a training step over ``layers`` recomputed expert
    layers, lowered for a TPU from this process (nothing is compiled):
    through the per-expert ladder, or (``grouped``, what a TPU runs since
    PR 44) through the grouped products."""
    class Lowered(Exception):
        pass

    def make_entry(self, *args, **kwargs):
        entry = _MAKE_ENTRY(self, *args, **kwargs)
        jitted = entry["jitted"]

        def lower(state, arrays):
            shapes = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype),
                (state, arrays))
            raise Lowered(jitted.trace(*shapes).lower(
                lowering_platforms=("tpu",)).as_text())
        entry["jitted"] = lower
        return entry

    monkeypatch.setattr(jit.StaticFunction, "_make_entry", make_entry)
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    monkeypatch.setitem(P._overrides, "moe_grouped", grouped)
    monkeypatch.setattr(moe_ops, "MIN_ROWS", 16)
    pt.seed(0)
    model = _Stack(layers, d, 128 if grouped else 32)
    o = opt.AdamW(learning_rate=1e-3, parameters=model.parameters())

    def step(u):
        loss = (model(u) ** 2).mean()
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    with pytest.raises(Lowered) as e:
        jit.to_static(step, models=[model], optimizers=[o])(
            pt.to_tensor(np.ones((1, 64, d), np.float32)))
    return str(e.value)


def test_kernel_instances_do_not_grow_with_layers_or_call_sites(
        monkeypatch):
    """64 tokens from ``MIN_ROWS`` 16: three rungs. A layer has three call
    sites (forward, recomputed forward, backward's ``dx``), each with a
    branch a rung. The module holds the kernel once a rung for the
    backward's call and once more for the forward's (``jax.checkpoint``
    re-stages the forward's inner jit as a jaxpr of its own) - and no more
    with three layers than with two. (With one layer nothing asks for the
    first layer's ``dx`` and its scatter is dead code: the forward's three
    alone.)"""
    assert moe_ops._ladder(64, 16) == (16, 32, 64)
    monitor.enable()
    reg = monitor.registry()
    counts = {}
    for layers in (1, 2, 3):
        before = int(reg.value("moe_experts.kernel_traced", 0))
        text = _lowered_step(monkeypatch, layers)
        assert int(reg.value("moe_experts.kernel_traced", 0)) - before \
            == layers
        counts[layers] = (text.count("tpu_custom_call"), len(re.findall(
            r"func\.func private @scatter_add", text)))
    assert counts[1] == (3, 3) and counts[2] == counts[3] == (6, 6), counts
    # the inner jit is called, not inlined: three rungs x (three layers'
    # forwards + two layers' dx)
    assert text.count("call @scatter_add") >= 3 * (3 + 2)


def test_at_a_width_the_tiles_do_not_fit_the_step_holds_no_custom_call(
        monkeypatch):
    """On a TPU (``interpret_mode`` False) the registry has the kernel on;
    96 lanes are no whole tile, and the call says so."""
    monitor.enable()
    reg = monitor.registry()
    before = int(reg.value("moe_experts.xla_traced", 0))
    text = _lowered_step(monkeypatch, 2, d=96)
    assert "tpu_custom_call" not in text and "scatter_add" not in text
    assert int(reg.value("moe_experts.xla_traced", 0)) - before == 2


# -- the same guard on the grouped path (PR 44) ------------------------------

GROUPED_KERNELS = ("hidden", "gmm", "hidden_bwd", "tgmm", "scatter_add")


def test_grouped_kernel_instances_do_not_grow_with_layers_or_call_sites(
        monkeypatch):
    """A layer calls ``hidden`` and the down ``gmm`` at two sites (forward,
    recomputed forward), ``hidden_bwd``, ``tgmm`` three times and the
    ``dx`` ``gmm`` in the backward, the scatter-add at three. The module
    holds each kernel once a distinct shape (the two ``gmm`` forms; the
    scatter-add once more for the forward's re-staged jaxpr) - and no more
    with three layers than with two, nor with the call sites."""
    monitor.enable()
    reg = monitor.registry()
    counts = {}
    for layers in (1, 2, 3):
        before = int(reg.value("moe_experts.grouped_traced", 0))
        text = _lowered_step(monkeypatch, layers, grouped=True)
        assert int(reg.value("moe_experts.grouped_traced", 0)) - before \
            == layers
        counts[layers] = (text.count("tpu_custom_call"), sorted(
            re.sub(r"_\d+$", "", name) for name in re.findall(
                r"func\.func private @(\w+)", text)
            if re.sub(r"_\d+$", "", name) in GROUPED_KERNELS))
    assert counts[2] == counts[3] == (7, [
        "gmm", "gmm", "hidden", "hidden_bwd", "scatter_add", "scatter_add",
        "tgmm"]), counts
    assert counts[1][0] <= 7
    # called, not inlined, from every layer
    assert text.count("call @hidden(") >= 3
    assert text.count("call @tgmm(") >= 3 * 3


def test_a_grouped_step_holds_no_switch_and_the_ladders_one_an_expert_scan(
        monkeypatch):
    """``lax.switch`` lowers to ``stablehlo.case``: the ladder's step has
    one in each scan over the experts (forward, recomputed forward,
    backward), the grouped step none - its loop is over rounds of rows."""
    ladder = _lowered_step(monkeypatch, 2)
    grouped = _lowered_step(monkeypatch, 2, grouped=True)
    assert ladder.count("stablehlo.case") >= 3
    assert "stablehlo.case" not in grouped
    assert "stablehlo.while" in grouped
