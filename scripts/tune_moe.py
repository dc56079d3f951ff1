"""The held experts' products on the chip, at the five MoE cells' shapes
and live row counts: the ladder (``ops/moe.py: _routed``, a ``lax.switch``
over capacities for every expert) against the grouped path
(``_routed_tiles``: one sort a layer, the kernels of
``ops/pallas/moe_grouped.py``), and the grouped products alone from three
makers - those kernels, jax's ``megablox`` (``gmm`` / ``tgmm``) and
``lax.ragged_dot`` / ``ragged_dot_general``:

    chiprun -- python -u scripts/tune_moe.py [--cells lfm2,sdar] [--iters 10]
        [--tiles 128,256,512] [--chunks 4096,8192,16384]

    lfm2          2 x 8,192 tokens x 2,048, 8 of 32 experts 1,792 wide held,
                  top-4, silu gate          (~20 k rows live a layer)
    smallthinker  16,384 x 2,560, 8 of 64, 768 wide, top-6, relu gate (~12 k)
    sdar          16,384 x 2,048, 16 of 128, 768 wide, top-8, silu gate (~12 k)
    nemotron      8,192 x 2,688, 8 of 128, 1,856 wide, top-6, relu^2 (~3 k;
                  no whole 128-lane tiles: the ladder alone is timed)
    joyai         8,192 x 2,048, 16 of 256, 768 wide, top-8, silu gate (~4 k)

``[layer]`` lines: ms a forward call and ms a forward + backward call of
the whole op (sort, gathers, products, combine), host clock around
``block_until_ready``, with the rows routed and the rows the products ran
over, and the largest difference of the grouped path's results from the
ladder's. ``[products]`` lines: the forward's products (two or three) and
the backward's (six to eight) alone, on rows gathered beforehand into the
grouped layout, one line a maker. ``--tiles`` / ``--chunks`` time the
grouped layer at other row tiles and round sizes than the op's own.

One process, one chip; a CPU number is no device number. The table goes
into PERF.md section 7, row 23; no cell runs this.
"""
import argparse
import importlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
from jax import lax             # noqa: E402

from paddle_tpu.ops import moe                       # noqa: E402
from paddle_tpu.ops import pallas as P               # noqa: E402
from paddle_tpu.ops.pallas import moe_grouped as G   # noqa: E402

# cell: tokens, d, f, held, the router's width that gives the cell's live
# rows (PERF.md section 5), top-k, gated, relu gate
SHAPES = {
    "lfm2": (16384, 2048, 1792, 8, 26, 4, True, False),
    "smallthinker": (16384, 2560, 768, 8, 64, 6, True, True),
    "sdar": (16384, 2048, 768, 16, 174, 8, True, False),
    "nemotron": (8192, 2688, 1856, 8, 128, 6, False, False),
    "joyai": (8192, 2048, 768, 16, 256, 8, True, False),
}
# (m, k, n) tiles of gmm and of tgmm: the package's default, and the
# largest that fit the 16 MiB of VMEM a kernel gets unasked
MEGABLOX_TILINGS = (((128, 128, 128), (128, 128, 128)),
                    ((512, 1024, 1024), (512, 512, 512)))
BF16, F32 = jnp.bfloat16, jnp.float32


def inputs(key, tokens, d, f, held, width, k, gated):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (1, tokens, d), BF16)
    _, experts = lax.top_k(jax.random.uniform(ks[1], (1, tokens, width)), k)
    weights = jax.random.uniform(ks[2], (1, tokens, k), F32)
    up = 0.02 * jax.random.normal(ks[3], (held, d, f), F32)
    down = 0.02 * jax.random.normal(ks[4], (held, f, d), F32)
    gate = (0.02 * jax.random.normal(ks[5], (held, d, f), F32),) \
        if gated else ()
    return (x, experts.astype(jnp.int32), weights, up, down) + gate


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / iters, out


def layer(a, routed, relu_gate, iters, **kw):
    """(ms forward, ms forward + backward, stats, (y, gradients)) of the
    whole op through ``routed``."""
    def fwd(*a):
        return routed(*a, first=0, dot_dtype=BF16, relu_gate=relu_gate, **kw)

    def loss(x, e, w, *ws):
        y, _ = fwd(x, e, w, *ws)
        return jnp.sum(jnp.square(y.astype(F32)))

    f_ms, (y, stats) = timed(jax.jit(fwd), a, iters)
    g_ms, grads = timed(jax.jit(jax.grad(
        loss, argnums=(0,) + tuple(range(2, len(a))))), a, iters)
    return f_ms, g_ms, stats, (y, grads)


def traced(cell, a, routed, relu_gate, iters, **kw):
    """A profiler trace of ``iters`` forward + backward calls through
    ``routed``, reduced as the benchmark reduces its own
    (``benchmark/reduce_trace.py``): device ms a call by operation."""
    from benchmark import reduce_trace

    def loss(x, e, w, *ws):
        y, _ = routed(x, e, w, *ws, first=0, dot_dtype=BF16,
                      relu_gate=relu_gate, **kw)
        return jnp.sum(jnp.square(y.astype(F32)))

    fn = jax.jit(jax.grad(loss, argnums=(0,) + tuple(range(2, len(a)))))
    jax.block_until_ready(fn(*a))
    where = os.path.join("chiprun_out", "tune_moe", f"{cell}.{routed.__name__}")
    os.makedirs(where, exist_ok=True)
    jax.profiler.start_trace(where)
    for _ in range(iters):
        out = fn(*a)
    jax.block_until_ready(out)
    jax.profiler.stop_trace()
    seen = reduce_trace.reduce_dir(where)
    print(f"[trace] cell={cell} path={routed.__name__} "
          f"busy_ms={1e3 * seen['busy_s'] / iters:.3f} a call of "
          f"{1e3 * seen['window_s'] / iters:.3f}; longest gaps (ms, after): "
          + " ".join(f"{1e3 * sec:.3f}:{what}"
                     for what, sec in seen["idle_gaps"][:6]), flush=True)
    for op, sec in seen["top_ops"][:40]:
        print(f"[trace]   {1e3 * sec / iters:8.3f} ms  {op}", flush=True)


def gaps(a, b):
    return [float(jnp.max(jnp.abs(p.astype(F32) - q.astype(F32))))
            for p, q in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


# -- the grouped products alone, from three makers --------------------------

def ours(group, live, tile):
    kw = dict(tile=tile, interpret=P.interpret_mode())

    def forward(xs, gate, ups, down, relu_gate):
        h = G.hidden(xs, gate, ups, group, live, relu_gate=relu_gate, **kw)
        return G.gmm((h,), (down,), group, live, transpose_rhs=False, **kw)

    def backward(xs, dy, gate, ups, down, relu_gate):
        cts, hg, dg = G.hidden_bwd(xs, dy, gate, ups, down, group, live,
                                   relu_gate=relu_gate, **kw)
        zeros = [jnp.zeros(w.shape, F32) for w in ups + (down,)]
        d_ws = [G.tgmm(a, b, z, group, live, **kw)
                for a, b, z in zip([xs] * len(ups) + [hg],
                                   list(cts) + [dy], zeros)]
        return (G.gmm(cts, ups, group, live, transpose_rhs=True, **kw),
                jnp.sum(dg, (0, 2)), d_ws)
    return forward, backward


def composed(gmm, tgmm):
    """Forward and backward out of a maker's ``gmm(lhs, rhs, transposed)``
    / ``tgmm(lhs, rhs)``, the element-wise stages as XLA fuses them."""
    def hidden(xs, ups, relu_gate):
        pre = [gmm(xs, w, False) for w in ups]
        if len(pre) == 1:
            act = jax.nn.relu(pre[0])
            return jnp.square(act), (act,)
        a, u = pre
        if relu_gate:
            act, slope = jax.nn.relu(a), (a > 0).astype(F32)
        else:
            sig = jax.nn.sigmoid(a)
            act = a * sig
            slope = sig + act * (1.0 - sig)
        return act * u, (act, u, slope)

    def forward(xs, gate, ups, down, relu_gate):
        h, _ = hidden(xs, ups, relu_gate)
        return gmm((h * gate).astype(xs.dtype), down, False)

    def backward(xs, dy, gate, ups, down, relu_gate):
        h, kept = hidden(xs, ups, relu_gate)
        d_h = gmm(dy, down, True)
        if len(ups) == 1:
            cts = [d_h * (2.0 * gate) * kept[0]]
        else:
            act, u, slope = kept
            cts = [d_h * gate * u * slope, d_h * gate * act]
        cts = [c.astype(xs.dtype) for c in cts]
        hg = (h * gate).astype(xs.dtype)
        dx = sum(gmm(c, w, True) for c, w in zip(cts, ups))
        return (dx, jnp.sum(d_h * h, -1),
                [tgmm(xs, c) for c in cts] + [tgmm(hg, dy)])
    return forward, backward


def megablox(sizes, tiling):
    # the package's ``gmm`` attribute is its differentiable wrapper; the
    # module of that name holds the two kernels
    mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")

    def gmm(lhs, rhs, transposed):
        return mb.gmm(lhs, rhs, sizes, F32, tiling[0],
                      transpose_rhs=transposed, interpret=P.interpret_mode())

    def tgmm(lhs, rhs):
        return mb.tgmm(lhs.T, rhs, sizes, F32, tiling[1],
                       interpret=P.interpret_mode())
    return composed(gmm, tgmm)


def ragged(sizes):
    numbers = lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(((0,), (0,)), ((), ())),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[])

    def gmm(lhs, rhs, transposed):
        return lax.ragged_dot(lhs, rhs.swapaxes(1, 2) if transposed else rhs,
                              sizes, preferred_element_type=F32)

    def tgmm(lhs, rhs):
        return lax.ragged_dot_general(lhs, rhs, sizes, numbers,
                                      preferred_element_type=F32)
    return composed(gmm, tgmm)


def products(cell, a, relu_gate, iters):
    """The grouped layout of ``a`` at the op's own tile, its rows gathered
    once, and each maker's products on them."""
    x, experts, weights, up, down, *gate = a
    tokens, d = x.shape[1], x.shape[2]
    tile, held = moe.ROW_TILE, up.shape[0]
    token, g, group, live, sizes, _ = jax.jit(
        lambda e, w: moe._layout(e[0], w[0], 0, held, tile, moe.CHUNK_ROWS))(
            experts, weights)
    rows = -(-int(live) * tile // moe.CHUNK_ROWS) * moe.CHUNK_ROWS
    padded = -(-sizes // tile) * tile
    at = jnp.minimum(token[:rows], tokens - 1)
    xs = x[0][at]
    dy = jax.random.normal(jax.random.key(1), (tokens, d), BF16)[at]
    g = g[:rows, None]
    ups, dn = tuple(w.astype(BF16) for w in gate + [up]), down.astype(BF16)
    live = jnp.reshape(live, (1,))
    makers = [("ours", ours(group[:rows // tile], live, tile))]
    makers += [(f"megablox{t[0]}".replace(" ", ""), megablox(padded, t))
               for t in MEGABLOX_TILINGS]
    makers.append(("ragged_dot", ragged(padded)))
    seen = {}
    for name, (forward, backward) in makers:
        t = time.perf_counter()
        try:
            f_ms, out = timed(jax.jit(
                lambda xs, g, ups, dn: forward(xs, g, ups, dn, relu_gate)),
                (xs, g, ups, dn), iters)
            b_ms, grads = timed(jax.jit(
                lambda xs, dy, g, ups, dn: backward(xs, dy, g, ups, dn,
                                                    relu_gate)),
                (xs, dy, g, ups, dn), iters)
        except Exception as e:      # noqa: BLE001 - a maker that cannot
            print(f"[products] cell={cell} maker={name} failed: "
                  f"{type(e).__name__}: {str(e)[:300]}", flush=True)
            continue
        n = int(live[0]) * tile     # rows past the live tiles hold anything
        seen[name] = (out[:n], grads[0][:n], grads[1][:n], grads[2])
        print(f"[products] cell={cell} maker={name} rows={n} of {rows} "
              f"fwd_ms={f_ms:.3f} bwd_ms={b_ms:.3f} "
              f"both_ms={f_ms + b_ms:.3f} "
              f"wall_s={time.perf_counter() - t:.1f}", flush=True)
    for name in seen:
        if name != "ours" and "ours" in seen:
            print(f"[products] cell={cell} largest |ours - {name}| by "
                  f"leaf: {' '.join(f'{v:.3g}' for v in gaps(seen['ours'], seen[name]))}",
                  flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cells", default=",".join(SHAPES))
    ap.add_argument("--tiles", default="")
    ap.add_argument("--chunks", default="")
    ap.add_argument("--skip-products", action="store_true")
    ap.add_argument("--precast", action="store_true",
                    help="time the grouped layer also with its matrices "
                         "cast to bfloat16 by XLA, a pass over each in HBM "
                         "once a layer and pass, as the ladder's per expert")
    ap.add_argument("--trace", action="store_true",
                    help="instead of timing: device ms by operation of a "
                         "forward + backward call, ladder and grouped")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny shapes off the chip: finds wrong paths, "
                         "and its times mean nothing")
    args = ap.parse_args()
    d0 = jax.devices()[0]
    print(f"[device] {d0.platform} {d0.device_kind!r}", flush=True)
    if args.rehearse:
        moe.ROW_TILE, moe.CHUNK_ROWS, moe.MIN_ROWS = 8, 32, 8
        for cell, (_, _, _, held, _, k, *form) in SHAPES.items():
            SHAPES[cell] = (64, 128, 128, held, 2 * held, min(k, 3), *form)
    elif d0.platform != "tpu":
        raise SystemExit("needs a TPU")
    own = (moe.ROW_TILE, moe.CHUNK_ROWS)
    sweeps = [own] + [(int(t), own[1]) for t in args.tiles.split(",") if t] \
        + [(own[0], int(c)) for c in args.chunks.split(",") if c]
    for cell in args.cells.split(","):
        *shape, relu_gate = SHAPES[cell]
        a = inputs(jax.random.key(44), *shape)
        if args.trace:
            traced(cell, a, moe._routed, relu_gate, args.iters, kernel=True)
            if G.supported(shape[1], shape[2], moe.ROW_TILE):
                traced(cell, a, moe._routed_tiles, relu_gate, args.iters)
            continue
        t = time.perf_counter()
        f_ms, g_ms, stats, ladder = layer(a, moe._routed, relu_gate,
                                          args.iters, kernel=True)
        print(f"[layer] cell={cell} path=ladder fwd_ms={f_ms:.3f} "
              f"fwd_bwd_ms={g_ms:.3f} layer_ms={f_ms + g_ms:.3f} "
              f"slots={int(stats[0])} rows_computed={int(stats[4])} "
              f"wall_s={time.perf_counter() - t:.1f}", flush=True)
        if not G.supported(shape[1], shape[2], own[0]):
            print(f"[layer] cell={cell} path=grouped: a width of "
                  f"{shape[1]} x {shape[2]} is no whole 128-lane tiles; "
                  f"F.moe_experts keeps the ladder there", flush=True)
            continue
        for tile, chunk in sweeps:
            moe.ROW_TILE, moe.CHUNK_ROWS = tile, chunk
            t = time.perf_counter()
            f_ms, g_ms, stats, grouped = layer(a, moe._routed_tiles,
                                               relu_gate, args.iters)
            print(f"[layer] cell={cell} path=grouped tile={tile} "
                  f"chunk={chunk} fwd_ms={f_ms:.3f} fwd_bwd_ms={g_ms:.3f} "
                  f"layer_ms={f_ms + g_ms:.3f} slots={int(stats[0])} "
                  f"rows_computed={int(stats[4])} "
                  f"wall_s={time.perf_counter() - t:.1f}", flush=True)
            if (tile, chunk) == own:
                print(f"[layer] cell={cell} largest |ladder - grouped| by "
                      f"leaf: {' '.join(f'{v:.3g}' for v in gaps(ladder, grouped))}",
                      flush=True)
        moe.ROW_TILE, moe.CHUNK_ROWS = own
        if args.precast:
            def precast(x, e, w, *ws, **kw):
                return moe._routed_tiles(
                    x, e, w, *[m.astype(BF16) for m in ws], **kw)
            t = time.perf_counter()
            f_ms, g_ms, stats, _ = layer(a, precast, relu_gate, args.iters)
            print(f"[layer] cell={cell} path=grouped_precast "
                  f"fwd_ms={f_ms:.3f} fwd_bwd_ms={g_ms:.3f} "
                  f"layer_ms={f_ms + g_ms:.3f} "
                  f"wall_s={time.perf_counter() - t:.1f}", flush=True)
        if not args.skip_products:
            products(cell, a, relu_gate, args.iters)


if __name__ == "__main__":
    main()
