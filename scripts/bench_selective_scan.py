"""Time ``F.selective_scan``'s kernel pair alone at the phi4_mini_flash
cell's shape (1 x 8,192 x 5,120 channels, state 16):

    chiprun -- python scripts/bench_selective_scan.py [--iters 20] \
        [--chunks 32,64,128] [--rehearse]

For each chunk of positions: ms a forward call and ms a forward + backward
call (every gradient asked for, the relayouts XLA makes around the kernels
included), and the largest difference of results and gradients from the
portable path (``ops/ssm.py: _selective_scan``) at a length it walks in
reasonable time (1,024). ``--rehearse`` walks the script tiny on a CPU
(interpret-mode kernels; no time is a device time there). One process,
one chip.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from paddle_tpu.ops.pallas import selective_scan as kernels     # noqa: E402
from paddle_tpu.ops.ssm import _selective_scan                  # noqa: E402


def operands(key, s, d, n):
    k = jax.random.split(key, 6)
    return (jax.random.normal(k[0], (1, s, d), jnp.bfloat16),
            jnp.exp(jax.random.uniform(k[1], (1, s, d), jnp.float32,
                                       np.log(1e-3), np.log(1e-1))),
            -jnp.broadcast_to(jnp.arange(1, n + 1, dtype=jnp.float32),
                              (d, n)),
            jax.random.normal(k[2], (1, s, n), jnp.bfloat16),
            jax.random.normal(k[3], (1, s, n), jnp.bfloat16),
            jnp.ones((d,), jnp.float32),
            jax.random.normal(k[4], (1, s, d), jnp.float32))


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / iters


def gap(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--chunks", default="32,64,128")
    ap.add_argument("--shape", default="8192,5120,16")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    s, d, n = (int(x) for x in args.shape.split(","))
    chunks = [int(c) for c in args.chunks.split(",")]
    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    if args.rehearse:
        s, d, n, chunks, args.iters = 32, 1024, 4, [8], 1
    elif device.platform != "tpu":
        raise SystemExit("needs a TPU (or --rehearse)")
    short = min(s, 1024)
    for chunk in chunks:
        def both(fn, *a):
            *ops, ct = a
            y, vjp = jax.vjp(lambda *o: fn(*o, chunk=chunk), *ops)
            return (y,) + vjp(ct.astype(y.dtype))

        ops = operands(jax.random.key(0), s, d, n)
        fwd = jax.jit(lambda *o: kernels.selective_scan(*o, chunk=chunk))
        ms_f = timed(fwd, ops[:-1], args.iters)
        ms_b = timed(jax.jit(lambda *a: both(kernels.selective_scan, *a)),
                     ops, args.iters)
        small = operands(jax.random.key(1), short, d, n)
        got = jax.jit(lambda *a: both(kernels.selective_scan, *a))(*small)
        want = jax.jit(lambda *a: both(_selective_scan, *a))(*small)
        gaps = " ".join(f"{gap(g, w):.1e}" for g, w in zip(got, want))
        print(f"chunk {chunk:4d}  fwd {ms_f:8.3f} ms  fwd+bwd {ms_b:8.3f} ms"
              f"  gaps (y dx ddt dA dB dC dD) {gaps}", flush=True)


if __name__ == "__main__":
    main()
