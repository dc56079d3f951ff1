"""Time ``F.gated_short_conv`` alone at the lfm2 cell's shape (2 x 8,192 x
3 x 2,048 bfloat16, 3 taps), three ways:

    chiprun -- python scripts/bench_gated_short_conv.py [--iters 50]

``kernels``: the pair of ``ops/pallas/causal_conv1d.py`` (``gated_conv_fwd``
/ ``gated_conv_bwd``); ``xla``: the op's portable path (``ops/ssm.py:
_gated_conv``, one float32 expression XLA fuses as it will);
``composed``: what the repo had before the op, ``c * F.causal_conv1d(b *
u, k)`` with the Mamba pair's kernels between two XLA gate fusions over
slices of ``bcx``. Prints ms a forward call and ms a forward + backward
call, the share of the HBM peak that 4 x and 7 x ``B S C`` bfloat16 values
are of each (``benchmark/lfm2_costs.py``), and the largest difference of
each path's results from the float32 oracle. One process, one chip. Needs
a TPU: a CPU number is no device number.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from paddle_tpu.ops.pallas import causal_conv1d as kernels      # noqa: E402
from paddle_tpu.ops.ssm import _gated_conv                      # noqa: E402

HBM_BYTES_PER_S = 819e9         # benchmark/peaks.json, TPU v5 lite


def composed(bcx, w):
    b, c, u = jnp.split(bcx, 3, axis=-1)
    return c * kernels.causal_conv1d(b * u, w, activation=None)


def timed(fn, args, iters):
    out = jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / iters, out


def gap(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--shape", default="2,8192,2048,3")
    args = ap.parse_args()
    bsz, s, c, taps = (int(n) for n in args.shape.split(","))
    device = jax.devices()[0]
    print("device", device.platform, device.device_kind, flush=True)
    if device.platform != "tpu":
        raise SystemExit("needs a TPU")
    k = jax.random.split(jax.random.key(0), 3)
    bcx = jax.random.normal(k[0], (bsz, s, 3 * c), jnp.bfloat16)
    w = jax.random.uniform(k[1], (c, taps), jnp.float32, -0.577, 0.577)
    ct = jax.random.normal(k[2], (bsz, s, c), jnp.bfloat16)
    n = bsz * s * c * 2
    oracle = jax.jit(lambda a, b, g: jax.vjp(_gated_conv, a, b)[1](g))
    want_y = _gated_conv(bcx.astype(jnp.float32), w)
    want_g = oracle(bcx.astype(jnp.float32), w, ct.astype(jnp.float32))
    for name, fn in (("kernels", kernels.gated_short_conv),
                     ("xla", _gated_conv), ("composed", composed)):
        fwd = jax.jit(fn)
        both = jax.jit(lambda a, b, g, fn=fn: jax.vjp(fn, a, b)[1](g))
        ms_f, y = timed(fwd, (bcx, w), args.iters)
        ms_b, grads = timed(both, (bcx, w, ct), args.iters)
        print(f"{name:9s} fwd {ms_f:7.3f} ms ({100 * 4 * n / HBM_BYTES_PER_S / (1e-3 * ms_f):5.1f} % of HBM peak at 4 n)  "
              f"fwd+bwd {ms_b:7.3f} ms ({100 * 11 * n / HBM_BYTES_PER_S / (1e-3 * ms_b):5.1f} % at 11 n)  "
              f"gaps y {gap(y, want_y):.2e} dbcx {gap(grads[0], want_g[0]):.2e} "
              f"dw {gap(grads[1], want_g[1]):.2e}", flush=True)


if __name__ == "__main__":
    main()
