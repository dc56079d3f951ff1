"""The grouped path of ``F.moe_experts`` (PERF.md section 6, PR 44), tiny on
the CPU with its kernels in interpret mode: rows sorted by expert once a
layer, each expert's rows padded to a row tile, one grouped product a
matrix and pass (``ops/pallas/moe_grouped.py``), against the plain loop
over the held experts that computes every token for every expert.
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

import paddle_tpu as pt                                         # noqa: E402
from paddle_tpu import monitor                                  # noqa: E402
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.ops import moe as moe_ops                       # noqa: E402
from paddle_tpu.ops import pallas as P                          # noqa: E402
from paddle_tpu.ops.pallas import moe_grouped_mod as G          # noqa: E402

TOKENS, D, WIDE, HELD, FIRST, EXPERTS, K = 40, 128, 128, 4, 2, 8, 2
TILE, CHUNK = 8, 32
FORMS = {"relu2": (False, False), "silu_gate": (True, False),
         "relu_gate": (True, True)}


@pytest.fixture()
def small_tiles(monkeypatch):
    """Row tiles of 8 and rounds of 32 rows: tens of rows then span
    several tiles, groups that end inside one, and several rounds."""
    monkeypatch.setattr(moe_ops, "ROW_TILE", TILE)
    monkeypatch.setattr(moe_ops, "CHUNK_ROWS", CHUNK)


def _routing(case):
    key = jax.random.key(11)
    if case == "spread":        # experts 0-7 chosen, 2-5 held: absent ones
        _, experts = jax.lax.top_k(jax.random.uniform(
            key, (1, TOKENS, EXPERTS)), K)
    elif case == "one_idle":    # held expert 3 draws no row
        _, experts = jax.lax.top_k(jax.random.uniform(
            key, (1, TOKENS, EXPERTS)).at[..., 3].set(-1.0), K)
    elif case == "all_to_one":  # every token chooses held expert 4 (and 5):
        experts = jnp.tile(jnp.asarray([4, 5]), (1, TOKENS, 1))  # the bound
    else:                       # whole tiles: 16 rows to each of 2, 3, 4, 5
        experts = jnp.tile(jnp.asarray([[2, 3], [4, 5], [3, 2], [5, 4], [0, 7]]),
                           (1, TOKENS // 5, 1))
    return experts.astype(jnp.int32)


def _inputs(form, dtype, case):
    gated, _ = FORMS[form]
    key = jax.random.key(5)
    x = jax.random.normal(key, (1, TOKENS, D)).astype(dtype)
    weights = jax.random.uniform(jax.random.fold_in(key, 2),
                                 (1, TOKENS, K), jnp.float32, 0.2, 1.0)
    ws = [0.3 * jax.random.normal(jax.random.fold_in(key, 3 + i), shape)
          for i, shape in enumerate([(HELD, D, WIDE), (HELD, WIDE, D)]
                                    + [(HELD, D, WIDE)] * gated)]
    return (x, _routing(case), weights, *ws)


def _plain(x, experts, weights, w_up, w_down, w_gate=None, *, first,
           dot_dtype, relu_gate):
    """Every token through every held expert, its weight 0 where the
    router did not choose it: ``ops/moe.py``'s roundings (operands in
    ``dot_dtype``, float32 sums, the weight multiplied in before the cast
    to the down product), no sort, no padding, no kernel."""
    rows = x.reshape(-1, x.shape[-1]).astype(dot_dtype)
    y = jnp.zeros(rows.shape, jnp.float32)
    for i in range(w_up.shape[0]):
        g = jnp.sum(jnp.where(experts.reshape(-1, K) == first + i,
                              weights.reshape(-1, K), 0.0), -1)[:, None]
        up = moe_ops._dot(rows, w_up[i].astype(dot_dtype), ((1,), (0,)))
        if w_gate is None:
            h = jnp.square(jax.nn.relu(up)) * g
        else:
            a = moe_ops._dot(rows, w_gate[i].astype(dot_dtype),
                             ((1,), (0,)))
            h = (jax.nn.relu(a) if relu_gate else jax.nn.silu(a)) * up * g
        y += moe_ops._dot(h.astype(dot_dtype), w_down[i].astype(dot_dtype),
                          ((1,), (0,)))
    return y.reshape(x.shape).astype(x.dtype), None


def _value_and_grads(fn, a, dtype, relu_gate):
    def loss(x, e, w, *ws):
        y, stats = fn(x, e, w, *ws, first=FIRST, dot_dtype=dtype,
                      relu_gate=relu_gate)
        return jnp.sum(jnp.square(y.astype(jnp.float32))), (y, stats)
    (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0,) + tuple(range(2, len(a))), has_aux=True))(*a)
    return y, stats, grads


CASES = [(form, dtype, "spread") for form in FORMS
         for dtype in ("f32", "bf16")] + [
    ("silu_gate", "bf16", "one_idle"), ("relu2", "f32", "one_idle"),
    ("silu_gate", "bf16", "all_to_one"), ("relu2", "f32", "all_to_one"),
    ("relu_gate", "bf16", "whole_tiles"), ("relu2", "f32", "whole_tiles")]


@pytest.mark.parametrize("form,dtype,case", CASES,
                         ids=["-".join(c) for c in CASES])
def test_grouped_experts_equal_the_plain_loop(small_tiles, form, dtype,
                                              case):
    """``y`` to the bit (the same products on the same rows, added in
    expert order) and the gradient of ``x``, of the router's weights and of
    every matrix to a float32 sum's reordering; ``stats`` count the rows
    routed, none dropped, and the rows the products ran over: each group
    rounded up to its row tile."""
    dtype = {"f32": jnp.float32, "bf16": jnp.bfloat16}[dtype]
    relu_gate = FORMS[form][1]
    a = _inputs(form, dtype, case)
    y, stats, grads = _value_and_grads(moe_ops._routed_tiles, a, dtype,
                                       relu_gate)
    y0, _, grads0 = _value_and_grads(_plain, a, dtype, relu_gate)
    assert y.dtype == dtype and float(jnp.max(jnp.abs(
        y.astype(jnp.float32)))) > 0
    np.testing.assert_array_equal(np.asarray(y, np.float32),
                                  np.asarray(y0, np.float32))
    for g, g0 in zip(grads, grads0):
        assert g.dtype == g0.dtype and g.shape == g0.shape
        # in bfloat16 the hand-written backward rounds dy to the products'
        # dtype, as the ladder's does; the plain loop's autodiff does not
        coarse = dtype == jnp.bfloat16
        g, g0 = np.asarray(g, np.float32), np.asarray(g0, np.float32)
        np.testing.assert_allclose(
            g, g0, rtol=2e-2 if coarse else 2e-5,
            atol=(1e-2 if coarse else 2e-6) * np.abs(g0).max())
    local = np.asarray(a[1]).reshape(-1) - FIRST
    sizes = np.bincount(local[(local >= 0) & (local < HELD)],
                        minlength=HELD)
    assert {"one_idle": sizes[1] == 0, "all_to_one": sizes.max() == TOKENS,
            "whole_tiles": not (sizes % TILE).any(),
            "spread": (sizes % TILE).any()}[case]
    assert len(moe_ops.MOE_STATS) == 5
    np.testing.assert_array_equal(
        np.asarray(stats), [sizes.sum(), 0, sizes.max(), 1,
                            (-(-sizes // TILE) * TILE).sum()])
    # the layout's static bound holds every token for every held expert it
    # may choose, in whole rounds: nothing can be dropped
    assert int(stats[4]) <= -(-(TOKENS * K + HELD * (TILE - 1)) // CHUNK) \
        * CHUNK


def test_the_layout_sorts_once_and_pads_to_the_tile(small_tiles):
    """Row by row: an expert's tokens in their order, then padding up to
    the tile, the next expert on a tile's first row; padding names a row
    past the tokens, its own in its tile, and weighs 0."""
    experts, weights = _routing("spread")[0], jnp.arange(
        1.0, TOKENS * K + 1).reshape(TOKENS, K)
    token, gate, group, live, sizes, slot = moe_ops._layout(
        experts, weights, FIRST, HELD, TILE, CHUNK)
    token, gate, group = (np.asarray(v) for v in (token, gate, group))
    assert token.shape == gate.shape == (128,) and group.shape == (16,)
    # every slot once, then the padding's numbers: a permutation
    np.testing.assert_array_equal(np.sort(np.asarray(slot)),
                                  np.arange(slot.size))
    assert slot.size == max(128, TOKENS * K + HELD * TILE)
    at = 0
    for e in range(HELD):
        mine = np.argwhere(np.asarray(experts) == FIRST + e)
        assert len(mine) == int(sizes[e])
        np.testing.assert_array_equal(token[at:at + len(mine)], mine[:, 0])
        np.testing.assert_array_equal(
            gate[at:at + len(mine)],
            np.asarray(weights)[mine[:, 0], mine[:, 1]])
        end = at + -(-len(mine) // TILE) * TILE
        np.testing.assert_array_equal(
            token[at + len(mine):end],
            TOKENS + np.arange(at + len(mine), end) % TILE)
        assert not gate[at + len(mine):end].any()
        assert (group[at // TILE:end // TILE] == e).all()
        at = end
    assert int(live) == at // TILE
    assert (token[at:] >= TOKENS).all() and not gate[at:].any()


def test_the_counter_says_the_grouped_path_was_traced(small_tiles):
    """``moe_experts.grouped_traced`` beside ``kernel_traced`` /
    ``xla_traced``: the registry's ``moe_grouped`` and the call's widths
    (whole 128-lane tiles of ``d``) choose, and the three exclude each
    other."""
    monitor.enable()
    reg = monitor.registry()

    def counts():
        return tuple(int(reg.value(f"moe_experts.{n}_traced", 0))
                     for n in ("grouped", "kernel", "xla"))

    def call(d):
        key = jax.random.key(0)
        a = (jax.random.normal(key, (1, TOKENS, d)), _routing("spread"),
             jnp.ones((1, TOKENS, K)),
             0.3 * jax.random.normal(jax.random.fold_in(key, 1),
                                     (HELD, d, 128)),
             0.3 * jax.random.normal(jax.random.fold_in(key, 2),
                                     (HELD, 128, d)))
        return F.moe_experts(*(pt.to_tensor(t) for t in a),
                             first_expert=FIRST)

    P.configure(moe_grouped=True)
    try:
        g0, k0, x0 = counts()
        y, stats = call(128)
        assert counts() == (g0 + 1, k0, x0)
        assert int(stats.numpy()[1]) == 0 and not int(stats.numpy()[4]) % TILE
        call(96)                            # no whole lane tile: the ladder
        assert counts() == (g0 + 1, k0, x0 + 1)
        P.configure(moe_grouped=None)       # auto: a CPU has no kernel path
        y0, _ = call(128)
        assert counts() == (g0 + 1, k0, x0 + 2)
        np.testing.assert_allclose(y.numpy(), y0.numpy(), rtol=1e-5,
                                   atol=1e-4)
    finally:
        P.configure(moe_grouped=None)


def test_the_kernels_tiles():
    """Blocks from the shapes: a weight block under 4 MiB a matrix, the
    widest whole-lane divisor; ``tgmm``'s accumulator the pair that
    re-reads least."""
    assert G.col_tile(1792, 2048, 2) == 896 and G.col_tile(768, 2560, 2) == 768
    assert G.col_tile(1920, 2688, 2) == 640 and G.col_tile(2048, 1792, 2) == 1024
    assert G.tgmm_tiles(2048, 1792) == (1024, 896)
    assert G.tgmm_tiles(768, 2560) == (768, 1280)
    assert G.supported(2048, 1792, 256) and not G.supported(2048 + 64, 768, 256)
    assert not G.supported(2688, 1856, 256)     # nemotron's: the ladder
