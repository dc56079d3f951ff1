"""paddle_tpu.ops.moe — routed mixture-of-experts ops.

No reference counterpart in Paddle Fluid 1.7. Two ops, each one pure-jax
impl through ``dispatch.apply``:

* ``moe_route`` — the router, over ALL experts the model has, float32
  throughout. Scores ``s`` are a sigmoid of each output (DeepSeek-V3,
  ``nemotron_h``; with a selection bias: top-k of ``s + b``) or a soft-max
  over the outputs (``qwen3_moe``, ``sdar_moe``); weights from ``s``
  alone, renormalised over the chosen and scaled.
* ``moe_experts`` — the part of the layer's result that the experts HELD
  HERE give (expert parallelism's local half: the layer is told which
  experts it holds, the router still ranges over all of them): two
  products an expert, ``W_down relu(W_up x)^2``; three with a gate,
  ``W_down (silu(W_gate x) * W_up x)``, or with ``activation="relu"``
  ``W_down (relu(W_gate x) * W_up x)``. Dropless by construction, on either
  of two paths whose cost follows the rows routed:

  **Grouped** (one TPU, ``d`` and the experts' width whole 128-lane tiles;
  ``_routed_tiles``). The ``tokens x k`` (token, slot) pairs are sorted by
  held expert ONCE a layer (``_layout``: one stable multi-operand sort,
  the slots of absent experts last), each expert's rows padded to a row
  tile of ``ROW_TILE`` rows and not to a capacity. Then ONE grouped
  product a matrix and pass over the sorted rows - the kernels of
  ``ops/pallas/moe_grouped.py``, whose grids follow the group sizes by a
  scalar-prefetched table (which expert's weight block a row tile reads;
  row tiles past the live rows are skipped) and which cast the float32
  matrices block by block in VMEM, with no pass over them in HBM -
  between one gather of the rows (and of ``dy``) and one in-place
  scatter-add (``ops/pallas/moe_scatter_add.py``). The layout's
  static bound is ``tokens x min(k, held)`` rows, since every token may
  choose only experts held here; a tenth to a third of that is live, so
  gather, products and scatter run in rounds of ``CHUNK_ROWS`` sorted rows,
  a loop whose trip count follows the live rows: nothing is dropped, and a
  layer holds one round's rows in HBM whatever the bound.

  **The ladder** (``_routed``: the CPU, under a mesh, a width that is no
  whole tile). The tokens of each held expert are brought to the front of
  a list (a stable sort an expert), the expert picks, inside the step, the
  smallest capacity of a ladder that holds its rows (``_ladder``: from
  ``MIN_ROWS`` up by doubling to the number of tokens, which is the most
  one expert can draw), gathers that many rows, runs its products on them
  and adds the result back to its tokens; the ladder's last rung holds
  every token, so no imbalance can overflow it.

  On both the backward pass is written by hand (``jax.custom_vjp``) over
  the same rows and makes the hidden activations again, so nothing of a
  round's or a rung's size is kept between the passes. What the absent
  experts would add is left out; no code stands in for their chips or for
  the exchange.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from ..dispatch import apply
from .nn_ops import _pscope

__all__ = ["moe_route", "moe_experts", "MOE_STATS"]

# what moe_experts counts each call, in this order (int32[5])
MOE_STATS = ("slots_routed_here", "slots_dropped", "expert_load_max", "calls",
             "rows_computed")

# The ladder's first rung. Under about 500 rows an expert's products on a
# v5e wait for its two weight matrices, not for its rows (2 flops a weight
# byte and row against the chip's 240 flops a byte), so smaller rungs would
# cost the same. (The grouped path has no rungs: ROW_TILE below.)
MIN_ROWS = 512


def moe_route(x, router_weight, bias=None, top_k=1, scale=1.0,
              scoring="sigmoid", name=None):
    """``(weights [..., k] float32, experts [..., k] int32)`` of a
    router: ``s = sigmoid(x W_r)``, or with ``scoring="softmax"`` the
    soft-max of ``x W_r`` over all the experts, in float32 whatever ``x``
    is; experts = top-k of ``s + bias`` (``bias`` a buffer: no gradient
    reaches it); ``weights = scale * s_i / (sum over the chosen of s +
    1e-20)``."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"moe_route: scoring {scoring!r} is neither "
                         f"'sigmoid' nor 'softmax'")
    score = jax.nn.sigmoid if scoring == "sigmoid" else jax.nn.softmax

    def impl(x, w, *b, top_k, scale):
        s = score(jnp.einsum(
            "...d,de->...e", x.astype(jnp.float32), w.astype(jnp.float32),
            precision="highest"))
        ranked = s if not b else s + jax.lax.stop_gradient(
            b[0].astype(jnp.float32))
        _, experts = jax.lax.top_k(ranked, top_k)
        # s at the chosen, as a select over the expert axis: the values of
        # take_along_axis to the bit, and a backward pass that is a dense
        # broadcast where the gather's scatters one element at a time
        picked = jnp.sum(jnp.where(
            experts[..., None] == jnp.arange(s.shape[-1]),
            s[..., None, :], 0.0), -1)
        weights = scale * picked / (jnp.sum(picked, -1, keepdims=True)
                                    + 1e-20)
        return weights, experts.astype(jnp.int32)

    args = (x, router_weight) if bias is None else (x, router_weight, bias)
    with _pscope("F.moe_route"):
        return apply(impl, args, dict(top_k=int(top_k), scale=float(scale)),
                     n_out=2, name="moe_route")


def _ladder(tokens, min_rows):
    """The capacities an expert's rows are padded to: ``min_rows``, x2,
    x4, ... and last ``tokens`` (a token chooses an expert once, so no
    expert draws more): under half of a rung is padding."""
    rungs, c = [], min_rows
    while c < tokens:
        rungs.append(c)
        c *= 2
    return tuple(rungs) + (tokens,)


def _dot(a, b, contract):
    return lax.dot_general(a, b, ((contract, ((), ()))),
                           preferred_element_type=jnp.float32)


def _on_rung(rung, ladder, run, *carry):
    """``run(capacity, *carry)`` at ``ladder[rung]``, chosen on the
    device."""
    if len(ladder) == 1:
        return run(ladder[0], *carry)
    return lax.switch(rung, [functools.partial(run, cap) for cap in ladder],
                      *carry)


def _accumulator(rows, kernel):
    """The scan's float32 sum over the experts, and the function that adds
    ``out`` to its rows ``at``: XLA's scatter-add into ``[tokens, d]``, or
    the in-place kernel over ``[tokens, 1, d]`` (``ops/pallas/
    moe_scatter_add.py``)."""
    tokens, d = rows.shape
    if not kernel:
        return (jnp.zeros((tokens, d), jnp.float32),
                lambda acc, at, out: acc.at[at].add(out,
                                                    unique_indices=True))
    from . import pallas
    add = functools.partial(pallas.moe_scatter_add_mod.scatter_add,
                            interpret=pallas.interpret_mode())
    return jnp.zeros((tokens, 1, d), jnp.float32), add


def _grouped_rows(rows, gate, ups, w_down, order, rung, ladder, dot_dtype,
                  kernel, relu_gate=False):
    """``y[t] = sum_e gate[e, t] W_down[e] h_e(rows[t])`` over the first
    ``ladder[rung[e]]`` tokens of ``order[e]``; ``gate`` is 0 for every
    token after an expert's own. ``ups`` is ``(w_up,)`` for ``h = relu(W_up
    x)^2`` and ``(w_gate, w_up)`` for the gated ``h = silu(W_gate x) *
    (W_up x)`` (``relu_gate``: ``relu(W_gate x) * (W_up x)``). float32
    [tokens, d]."""
    y, add_rows = _accumulator(rows, kernel)

    def expert(y, xs):
        ups, down, ids, g, r = xs

        def run(cap, y):
            at = ids[:cap]
            if len(ups) == 1:
                h = _dot(rows[at], ups[0].astype(dot_dtype), ((1,), (0,)))
                h = jnp.square(jax.nn.relu(h)) * g[at][:, None]
            else:
                x = rows[at]
                a, u = (_dot(x, w.astype(dot_dtype), ((1,), (0,)))
                        for w in ups)
                act = jax.nn.relu(a) if relu_gate else jax.nn.silu(a)
                h = act * u * g[at][:, None]
            out = _dot(h.astype(dot_dtype), down.astype(dot_dtype),
                       ((1,), (0,)))
            return add_rows(y, at, out)

        return _on_rung(r, ladder, run, y), None

    y = lax.scan(expert, y, (ups, w_down, order, gate, rung))[0]
    return y.reshape(rows.shape)


_grouped = jax.custom_vjp(_grouped_rows, nondiff_argnums=(6, 7, 8, 9))


def _grouped_fwd(rows, gate, ups, w_down, order, rung, ladder, dot_dtype,
                 kernel, relu_gate=False):
    y = _grouped_rows(rows, gate, ups, w_down, order, rung, ladder,
                      dot_dtype, kernel, relu_gate)
    return y, (rows, gate, ups, w_down, order, rung)


def _grouped_bwd(ladder, dot_dtype, kernel, relu_gate, saved, dy):
    rows, gate, ups, w_down, order, rung = saved
    f32 = jnp.float32
    dx, add_rows = _accumulator(rows, kernel)

    def expert(dx, xs):
        ups, down, ids, g, r = xs

        def run(cap, dx):
            at = ids[:cap]
            x, ga, dyr = rows[at], g[at][:, None], dy[at].astype(dot_dtype)
            if len(ups) == 1:
                upc, downc = ups[0].astype(dot_dtype), down.astype(dot_dtype)
                act = jax.nn.relu(_dot(x, upc, ((1,), (0,))))
                h = jnp.square(act)
                d_h = _dot(dyr, downc, ((1,), (1,)))            # [cap, f]
                d_down = _dot((h * ga).astype(dot_dtype), dyr, ((0,), (0,)))
                d_gate = jnp.zeros(g.shape, f32).at[at].set(
                    jnp.sum(d_h * h, -1), unique_indices=True)
                d_pre = (d_h * (2.0 * ga) * act).astype(dot_dtype)
                d_up = _dot(x, d_pre, ((0,), (0,)))
                dx = add_rows(dx, at, _dot(d_pre, upc, ((1,), (1,))))
                return dx, (d_up,), d_down, d_gate
            gatec, upc = (w.astype(dot_dtype) for w in ups)
            downc = down.astype(dot_dtype)
            a = _dot(x, gatec, ((1,), (0,)))
            u = _dot(x, upc, ((1,), (0,)))
            if relu_gate:
                act = jax.nn.relu(a)
            else:
                sig = jax.nn.sigmoid(a)
                act = a * sig                                   # silu(a)
            h = act * u
            d_h = _dot(dyr, downc, ((1,), (1,)))                # [cap, f]
            d_down = _dot((h * ga).astype(dot_dtype), dyr, ((0,), (0,)))
            d_gate = jnp.zeros(g.shape, f32).at[at].set(
                jnp.sum(d_h * h, -1), unique_indices=True)
            d_h = d_h * ga
            d_a = (d_h * u * ((a > 0).astype(f32) if relu_gate
                              else sig + act * (1.0 - sig))).astype(dot_dtype)
            d_u = (d_h * act).astype(dot_dtype)
            dx = add_rows(dx, at, _dot(d_a, gatec, ((1,), (1,)))
                          + _dot(d_u, upc, ((1,), (1,))))
            return (dx, (_dot(x, d_a, ((0,), (0,))),
                         _dot(x, d_u, ((0,), (0,)))), d_down, d_gate)

        dx, d_ups, d_down, d_gate = _on_rung(r, ladder, run, dx)
        return dx, (d_ups, d_down, d_gate)

    dx, (d_ups, d_down, d_gate) = lax.scan(
        expert, dx, (ups, w_down, order, gate, rung))
    return (dx.reshape(rows.shape).astype(rows.dtype), d_gate,
            tuple(d.astype(w.dtype) for d, w in zip(d_ups, ups)),
            d_down.astype(w_down.dtype), None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


# -- the grouped path: rows sorted by expert once, one product a matrix ----

# Rows an expert's group is padded to in the sorted layout: what the
# products run over is each group rounded up to this, not to a rung. The
# kernels keep an expert's weight block in VMEM while its row tiles go by
# (ops/pallas/moe_grouped.py), so a small tile costs no weight traffic.
ROW_TILE = 256
# Sorted rows that one round of gather, products and scatter holds. The
# layout's static bound is tokens x min(k, held) rows (dropless: every
# token may choose only experts held here) where a tenth to a third of
# that is live, so the rounds are a loop whose count follows the live
# rows, and what a layer keeps in HBM is one round's rows whatever the
# bound.
CHUNK_ROWS = 8192


def _layout(experts, weights, first, held, tile, chunk):
    """The sorted layout of a call: the (token, slot) pairs whose expert
    is held here, expert by expert in token order, each expert's rows
    padded to whole tiles of ``tile`` rows - by ONE stable sort, of the
    ``tokens x k`` slots together with ``tile`` rows of padding an expert:
    the padding an expert needs sorts behind its rows, the rest and the
    slots of absent experts behind everything. The router's weights and
    the slots' own numbers ride along, so nothing is gathered or scattered
    one element at a time.

    ``(token, gate, group, live, sizes, slot)``: for each row of the
    layout its token (a row of padding: ``tokens + its place in its
    tile``, so that a tile never names a row twice) and its router weight
    (padding: 0), int32 / float32 [rows], ``rows`` the static bound
    ``tokens x min(k, held)`` + padding in whole chunks; for each row tile
    the expert it belongs to, int32 [rows / tile]; the tiles that hold
    rows; the rows of each expert, int32 [held]; and every sorted entry's
    slot (padding: numbers past the slots), which sorts a gradient by row
    back into ``weights``' order."""
    tokens, k = experts.shape
    slots, pads = tokens * k, held * tile
    local = experts.reshape(slots) - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held)
    sizes = jnp.sum(key[None] == jnp.arange(held)[:, None], 1,
                    dtype=jnp.int32)
    padded = -(-sizes // tile) * tile
    bound = tokens * min(k, held) + held * (tile - 1)
    rows = -(-bound // chunk) * chunk
    dead = max(rows - slots - pads, 0)
    of_pad = jnp.repeat(jnp.arange(held, dtype=jnp.int32), tile)
    needed = jnp.tile(jnp.arange(tile, dtype=jnp.int32), held) \
        < jnp.repeat(padded - sizes, tile)
    last = jnp.full((dead,), 2 * held, jnp.int32)
    keys, slot, gate = lax.sort((
        jnp.concatenate([2 * key, jnp.where(needed, 2 * of_pad + 1,
                                            2 * held), last]),
        jnp.arange(slots + pads + dead, dtype=jnp.int32),
        jnp.concatenate([jnp.where(here, weights.reshape(slots).astype(
            jnp.float32), 0.0), jnp.zeros((pads + dead,), jnp.float32)])),
        num_keys=1, is_stable=True)
    keys, at = keys[:rows], jnp.arange(rows, dtype=jnp.int32)
    token = jnp.where((keys % 2 == 0) & (keys < 2 * held),
                      slot[:rows] // k, tokens + at % tile)
    group = jnp.minimum(keys[::tile] // 2, held - 1)
    return token, gate[:rows], group, jnp.sum(padded) // tile, sizes, slot


def _rounds(token, gate, group, live, tile, chunk, body, carry):
    """``body(carry, token, gate, group, live)`` over the chunks of the
    layout that hold rows, each with its slice of the layout's tables."""
    per = chunk // tile

    def one(c, carry):
        return body(carry,
                    lax.dynamic_slice(token, (c * chunk,), (chunk,)),
                    lax.dynamic_slice(gate, (c * chunk,), (chunk,)),
                    lax.dynamic_slice(group, (c * per,), (per,)),
                    jnp.clip(live - c * per, 0, per).reshape(1), c)

    return lax.fori_loop(0, -(-live // per), one, carry)


def _sum_rows(tokens, tile, d):
    """The float32 sum over a call's rows, ``[tokens + tile, 1, d]`` (the
    last ``tile`` rows take the padding), and ``add(acc, token, rows)``."""
    from . import pallas
    add = functools.partial(
        pallas.moe_scatter_add_mod.scatter_add,
        tile=pallas.moe_scatter_add_mod.row_tile(tile, d),
        interpret=pallas.interpret_mode())
    return jnp.zeros((tokens + tile, 1, d), jnp.float32), add


def _tiles_rows(rows, weights, ups, w_down, experts, first, tile, chunk,
                dot_dtype, relu_gate):
    return _tiles_fwd(rows, weights, ups, w_down, experts, first, tile,
                      chunk, dot_dtype, relu_gate)[0]


_tiles = jax.custom_vjp(_tiles_rows, nondiff_argnums=(5, 6, 7, 8, 9))


def _tiles_fwd(rows, weights, ups, w_down, experts, first, tile, chunk,
               dot_dtype, relu_gate):
    """``(y, stats)``: ``y[t] = sum over the layout's rows r of token t of
    gate[r] W_down[g(r)] h_{g(r)}(rows[t])``, float32 [tokens, d]."""
    from . import pallas
    G, interpret = pallas.moe_grouped_mod, pallas.interpret_mode()
    tokens, d = rows.shape
    token, gate, group, live, sizes, slot = _layout(
        experts, weights, first, w_down.shape[0], tile, chunk)
    y, add_rows = _sum_rows(tokens, tile, d)

    def body(y, token, gate, group, live, _):
        xs = rows[jnp.minimum(token, tokens - 1)]
        # (the kernels cast the float32 matrices block by block in VMEM:
        # no pass over them in HBM, where the ladder makes one an expert)
        h = G.hidden(xs, gate[:, None], ups, group, live, tile=tile,
                     relu_gate=relu_gate, interpret=interpret)
        out = G.gmm((h,), (w_down,), group, live, tile=tile,
                    transpose_rhs=False, interpret=interpret)
        return add_rows(y, token, out)

    y = _rounds(token, gate, group, live, tile, chunk, body, y)
    stats = jnp.stack([jnp.sum(sizes), jnp.zeros((), jnp.int32),
                       jnp.max(sizes), jnp.ones((), jnp.int32),
                       live * tile])
    return (y[:tokens].reshape(tokens, d), stats), (
        rows, weights, ups, w_down, token, gate, group, live, slot)


def _tiles_bwd(first, tile, chunk, dot_dtype, relu_gate, saved, cotangent):
    from . import pallas
    G, interpret = pallas.moe_grouped_mod, pallas.interpret_mode()
    rows, weights, ups, w_down, token, gate, group, live, slot = saved
    tokens, d = rows.shape
    f32 = jnp.float32
    dy = cotangent[0].astype(dot_dtype)
    dx, add_rows = _sum_rows(tokens, tile, d)
    kw = dict(tile=tile, interpret=interpret)

    def body(carry, token, gate, group, live, c):
        dx, d_gate, d_ups, d_down = carry
        at = jnp.minimum(token, tokens - 1)
        xs, dyr = rows[at], dy[at]
        cotangents, hg, dg = G.hidden_bwd(
            xs, dyr, gate[:, None], ups, w_down, group, live,
            relu_gate=relu_gate, **kw)
        d_down = G.tgmm(hg, dyr, d_down, group, live, **kw)
        d_ups = tuple(G.tgmm(xs, ct, acc, group, live, **kw)
                      for ct, acc in zip(cotangents, d_ups))
        dx = add_rows(dx, token, G.gmm(cotangents, ups, group, live,
                                       transpose_rhs=True, **kw))
        # a tile past the live ones was never written
        dg = jnp.where(jnp.arange(chunk) < live * tile,
                       jnp.sum(dg, (0, 2)), 0.0)
        return (dx, lax.dynamic_update_slice(d_gate, dg, (c * chunk,)),
                d_ups, d_down)

    dx, d_gate, d_ups, d_down = _rounds(
        token, gate, group, live, tile, chunk, body,
        (dx, jnp.zeros(slot.shape, f32),
         tuple(jnp.zeros(w.shape, f32) for w in ups),
         jnp.zeros(w_down.shape, f32)))
    # by row -> by slot: the sort's inverse is a sort by the slots' numbers;
    # the padding's numbers lie past the slots and fall off the end
    d_weights = lax.sort((slot, d_gate), num_keys=1)[1][:weights.size]
    return (dx[:tokens].reshape(tokens, d).astype(rows.dtype),
            d_weights.reshape(weights.shape).astype(weights.dtype),
            tuple(g.astype(w.dtype) for g, w in zip(d_ups, ups)),
            d_down.astype(w_down.dtype), None)


_tiles.defvjp(_tiles_fwd, _tiles_bwd)


def _routed_tiles(x, experts, weights, w_up, w_down, w_gate=None, *, first,
                  dot_dtype, relu_gate=False):
    lead, d = x.shape[:-1], x.shape[-1]
    rows = x.reshape(-1, d)
    tokens, k = rows.shape[0], experts.shape[-1]
    ups = (w_up,) if w_gate is None else (w_gate, w_up)
    y, stats = _tiles(rows.astype(dot_dtype), weights.reshape(tokens, k),
                      ups, w_down, experts.reshape(tokens, k), first,
                      ROW_TILE, CHUNK_ROWS, dot_dtype, relu_gate)
    return y.reshape(*lead, d).astype(x.dtype), stats


def _routed(x, experts, weights, w_up, w_down, w_gate=None, *, first,
            dot_dtype, kernel=False, relu_gate=False):
    f32 = jnp.float32
    lead, d = x.shape[:-1], x.shape[-1]
    rows = x.reshape(-1, d)
    tokens, k = rows.shape[0], experts.shape[-1]
    held = w_up.shape[0]
    local = experts.reshape(tokens, k) - first
    chose = local[None] == jnp.arange(held)[:, None, None]    # [held, T, k]
    # gate[e, t]: token t's weight for held expert e, 0 where not chosen
    gate = jnp.sum(jnp.where(chose, weights.reshape(1, tokens, k)
                             .astype(f32), 0.0), -1)
    chose = jnp.any(chose, -1)
    sizes = jnp.sum(chose, 1, dtype=jnp.int32)        # rows of each expert
    # each expert's own tokens first, in their order
    order = jnp.argsort(~chose, axis=1, stable=True).astype(jnp.int32)
    ladder = _ladder(tokens, MIN_ROWS)
    rung = jnp.searchsorted(jnp.asarray(ladder, jnp.int32), sizes)
    rung = jnp.minimum(rung, len(ladder) - 1).astype(jnp.int32)
    ups = (w_up,) if w_gate is None else (w_gate, w_up)
    y = _grouped(rows.astype(dot_dtype), gate, ups, w_down, order, rung,
                 ladder, dot_dtype, kernel, relu_gate)
    computed = jnp.asarray(ladder, jnp.int32)[rung]
    stats = jnp.stack([jnp.sum(sizes),
                       jnp.sum(jnp.maximum(sizes - computed, 0)),
                       jnp.max(sizes), jnp.ones((), jnp.int32),
                       jnp.sum(computed)])
    return y.reshape(*lead, d).astype(x.dtype), stats


def moe_experts(x, experts, weights, w_up, w_down, first_expert=0,
                w_gate=None, activation="silu", name=None):
    """``(y, stats)``: ``y[t] = sum over t's chosen experts i that are
    held here of weights[t, i] * W_down[i] h_i(x[t])``, with ``h_i(x) =
    relu(W_up[i] x)^2``, or, given ``w_gate``, the gated ``silu(W_gate[i]
    x) * (W_up[i] x)`` (``activation="relu"``: ``relu(W_gate[i] x) *
    (W_up[i] x)``).

    ``experts`` / ``weights`` [..., k] from :func:`moe_route`, over all the
    model's experts; ``w_up`` (and ``w_gate``) [held, d, f] and ``w_down``
    [held, f, d] are the experts ``first_expert .. first_expert + held``.
    ``stats`` is
    int32[5], :data:`MOE_STATS`: slots routed here; slots dropped (0 on
    either path: the layout's bound, or the ladder's last rung, holds
    every token); the fullest expert's rows; 1; and the rows the products
    ran over, padding included - each expert's rows rounded up to
    :data:`ROW_TILE` on the grouped path, to its rung (from
    :data:`MIN_ROWS` up by doubling) on the ladder. Under
    ``amp.auto_cast`` the products take the compute dtype's operands and
    accumulate in float32.

    On one TPU, where ``d`` and the experts' width are whole 128-lane
    tiles, the call takes the grouped path (module docstring): one sort a
    layer, one grouped product a matrix and pass
    (``ops/pallas/moe_grouped.py``). Otherwise the ladder, an expert's rows
    added to the sum in place by the kernel of
    ``ops/pallas/moe_scatter_add.py`` on one TPU and by XLA's scatter-add
    on the CPU, under a mesh and at a ``d`` that is no whole tile. The
    counters ``moe_experts.grouped_traced`` / ``moe_experts.kernel_traced``
    / ``moe_experts.xla_traced`` say which a call site traced."""
    from .. import amp, monitor
    from . import pallas
    if activation not in ("silu", "relu") or (
            activation == "relu" and w_gate is None):
        raise ValueError(f"moe_experts: activation {activation!r} is "
                         f"neither 'silu' nor, beside w_gate, 'relu'")
    dot_dtype = amp.compute_dtype() if amp.is_enabled() else None
    # read off the call, as ssd_scan: the grouped kernels where their
    # blocks fit its widths, else the ladder with the scatter-add kernel
    # where its tiles fit every rung - each where the registry has it on
    tokens, d = math.prod(x.shape[:-1]), int(x.shape[-1])
    scatter = pallas.moe_scatter_add_mod
    grouped = (pallas.enabled("moe_grouped")
               and pallas.moe_grouped_mod.supported(
                   d, int(w_up.shape[2]), ROW_TILE)
               and scatter.row_tile(ROW_TILE, d) is not None)
    kernel = not grouped and (
        pallas.enabled("moe_scatter_add")
        and scatter.supported(d, _ladder(tokens, MIN_ROWS)))
    monitor.counter("moe_experts.grouped_traced" if grouped
                    else "moe_experts.kernel_traced" if kernel
                    else "moe_experts.xla_traced").inc()

    def impl(x, experts, weights, w_up, w_down, *gate, first):
        kw = dict(first=first, dot_dtype=dot_dtype or jnp.result_type(x),
                  relu_gate=activation == "relu")
        if grouped:
            return _routed_tiles(x, experts, weights, w_up, w_down, *gate,
                                 **kw)
        return _routed(x, experts, weights, w_up, w_down, *gate,
                       kernel=kernel, **kw)

    args = (x, experts, weights, w_up, w_down)
    with _pscope("F.moe_experts"):
        return apply(impl, args if w_gate is None else args + (w_gate,),
                     dict(first=int(first_expert)), n_out=2,
                     name="moe_experts")
