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
  experts it holds, the router still ranges over all of them). Grouped
  products whose cost follows the rows routed: the tokens of each held
  expert are brought to the front of a list (a stable sort), the expert
  picks, inside the step, the smallest capacity of a ladder that holds
  its rows (``_ladder``: from ``MIN_ROWS`` up by doubling to the number
  of tokens, which is the most one expert can draw), gathers that
  many rows, runs its products on them (two, ``W_down relu(W_up x)^2``;
  three with a gate, ``W_down (silu(W_gate x) * W_up x)``, or with
  ``activation="relu"`` ``W_down (relu(W_gate x) * W_up x)``) and adds the
  result back to its tokens. Dropless by construction: the ladder's last rung holds
  every token, so no imbalance can overflow it. The backward pass is
  written by hand (``jax.custom_vjp``) over the same rows and makes the
  hidden activations again, so nothing of a rung's size is kept between
  the passes. What the absent experts would add is left out; no code
  stands in for their chips or for the exchange.
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

# Under about 500 rows an expert's products on a v5e wait for its two
# weight matrices, not for its rows (2 flops a weight byte and row against
# the chip's 240 flops a byte), so smaller rungs would cost the same.
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
    int32[5], :data:`MOE_STATS`: slots routed here; slots a rung did not
    hold (0: the last rung holds every token); the fullest expert's rows;
    1; and the rows the products ran over, padding included (the ladder
    starts at :data:`MIN_ROWS`). Under ``amp.auto_cast`` the products
    take the compute dtype's operands and accumulate in float32.

    On one TPU an expert's rows are added to the sum in place, by the
    kernel of ``ops/pallas/moe_scatter_add.py``; on the CPU, under a mesh
    and at a width that is no whole 128-lane tile, by XLA's scatter-add.
    The counters ``moe_experts.kernel_traced`` / ``moe_experts.xla_traced``
    say which a call site traced."""
    from .. import amp, monitor
    from . import pallas
    if activation not in ("silu", "relu") or (
            activation == "relu" and w_gate is None):
        raise ValueError(f"moe_experts: activation {activation!r} is "
                         f"neither 'silu' nor, beside w_gate, 'relu'")
    dot_dtype = amp.compute_dtype() if amp.is_enabled() else None
    # read off the call, as ssd_scan: the kernel where its tiles fit every
    # rung of this call's ladder and the registry has it on
    tokens = math.prod(x.shape[:-1])
    kernel = (pallas.enabled("moe_scatter_add")
              and pallas.moe_scatter_add_mod.supported(
                  int(x.shape[-1]), _ladder(tokens, MIN_ROWS)))
    monitor.counter("moe_experts.kernel_traced" if kernel
                    else "moe_experts.xla_traced").inc()

    def impl(x, experts, weights, w_up, w_down, *gate, first):
        return _routed(x, experts, weights, w_up, w_down, *gate, first=first,
                       dot_dtype=dot_dtype or jnp.result_type(x),
                       kernel=kernel, relu_gate=activation == "relu")

    args = (x, experts, weights, w_up, w_down)
    with _pscope("F.moe_experts"):
        return apply(impl, args if w_gate is None else args + (w_gate,),
                     dict(first=int(first_expert)), n_out=2,
                     name="moe_experts")
