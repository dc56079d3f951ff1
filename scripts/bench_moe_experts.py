"""Time ``F.moe_experts``' grouped products alone, at the three MoE cells'
shapes, with the in-place scatter-add kernel and with XLA's scatter-add:

    chiprun -- python scripts/bench_moe_experts.py [--iters 20]

One process, one chip. Prints, for each shape and path, ms a forward call
and ms a forward + backward call (what a recomputed block runs is one of
each), the rows the rungs computed, and the largest difference between
the two paths' results. Needs a TPU: a CPU number is no device number.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from paddle_tpu.ops import moe  # noqa: E402

# cell: tokens, d, f, held, router width that gives the cell's rows an
# expert (PERF.md section 5), top-k, gated
SHAPES = {
    "sdar": (16384, 2048, 768, 16, 174, 8, True),
    "joyai": (8192, 2048, 768, 16, 256, 8, True),
    "nemotron": (8192, 2688, 1856, 8, 100, 6, False),
}


def inputs(key, tokens, d, f, held, width, k, gated):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (1, tokens, d), jnp.bfloat16)
    _, experts = jax.lax.top_k(jax.random.uniform(ks[1], (1, tokens, width)),
                               k)
    weights = jax.random.uniform(ks[2], (1, tokens, k), jnp.float32)
    up = 0.02 * jax.random.normal(ks[3], (held, d, f), jnp.float32)
    down = 0.02 * jax.random.normal(ks[4], (held, f, d), jnp.float32)
    gate = (0.02 * jax.random.normal(ks[5], (held, d, f), jnp.float32),) \
        if gated else ()
    return (x, experts.astype(jnp.int32), weights, up, down) + gate


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / iters, out


def bench(a, kernel, iters):
    """(ms forward, ms forward + backward, stats, (y, gradients)) of one
    path at inputs ``a``."""
    def fwd(*a):
        return moe._routed(*a, first=0, dot_dtype=jnp.bfloat16,
                           kernel=kernel)

    def loss(x, e, w, *ws):
        y, _ = fwd(x, e, w, *ws)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    f_ms, (y, stats) = timed(jax.jit(fwd), a, iters)
    g_ms, grads = timed(jax.jit(jax.grad(
        loss, argnums=(0,) + tuple(range(2, len(a))))), a, iters)
    return f_ms, g_ms, stats, (y, grads)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--cells", default=",".join(SHAPES))
    args = ap.parse_args()
    d0 = jax.devices()[0]
    print(f"[device] {d0.platform} {d0.device_kind!r}", flush=True)
    if d0.platform != "tpu":
        raise SystemExit("needs a TPU")
    for cell in args.cells.split(","):
        a = inputs(jax.random.key(38), *SHAPES[cell])
        seen = {}
        for kernel in (False, True):
            t = time.perf_counter()
            f_ms, g_ms, stats, seen[kernel] = bench(a, kernel, args.iters)
            print(f"[{cell}] path={'kernel' if kernel else 'xla'} "
                  f"fwd_ms={f_ms:.3f} fwd_bwd_ms={g_ms:.3f} "
                  f"layer_ms={f_ms + g_ms:.3f} slots={int(stats[0])} "
                  f"rows_computed={int(stats[4])} "
                  f"wall_s={time.perf_counter() - t:.1f}", flush=True)
        print(f"[{cell}] largest |xla - kernel| by leaf: "
              f"{' '.join(f'{g:.3g}' for g in gaps(*seen.values()))}",
              flush=True)


def gaps(a, b):
    return [float(jnp.max(jnp.abs(p.astype(jnp.float32)
                                  - q.astype(jnp.float32))))
            for p, q in zip(jax.tree_util.tree_leaves(a),
                            jax.tree_util.tree_leaves(b))]


if __name__ == "__main__":
    main()
