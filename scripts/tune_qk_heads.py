"""Time ``F.qk_heads`` alone at the two long grouped-query cells' shapes,
the kernel pair of ``ops/pallas/qk_heads.py`` against the portable chain
(``ops/nn_ops.py: _qk_heads``: ``_rms_norm``, a transpose, ``_rotate``,
which is what ``GroupedQueryAttention._heads`` ran before PR 45):

    chiprun -- python3 scripts/tune_qk_heads.py [--iters 10] \
        [--cells sdar_q,sdar_k,st_q,st_k] [--tiles 128x32,256x16,512x8]

``sdar_*``: 1 x 16,384 rows x 32 / 4 heads of 128, bfloat16, head norm +
rotation at given positions (both copies of token i at position i);
``st_*``: 1 x 16,384 x 28 / 4 x 128, rotation alone, positions counted.
Prints ms a forward call and ms a forward + backward call of each path,
the share of the HBM peak that one read and one write of the array
(forward) and five passes over it (forward + backward: the gradient and
the input read again, the input's gradient written) are of each, and the
largest difference of the kernels' results from the chain's. ``--tiles``
times the two kernels alone at each ``rows x heads`` a program that the
shape allows (``_forward`` / ``_backward`` called directly). A call takes
a few tenths of a millisecond, less than the host needs to send one, so
the times are the DEVICE's: a profiler trace of ``--iters`` calls, reduced
as the benchmark reduces its own (``benchmark/reduce_trace.py``), the
device's busy time a call — every instruction of the call's program, the
tables' cosines and sines included. The lines also go to
``chiprun_out/tune_qk_heads.txt``. One process, one chip. Needs a TPU: a
CPU number is no device number (``--rehearse`` walks it tiny, interpreted,
and prints no time).
"""
import argparse
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from paddle_tpu.ops import nn_ops as F                  # noqa: E402
from paddle_tpu.ops import pallas                       # noqa: E402
from paddle_tpu.ops.pallas import qk_heads as kernels   # noqa: E402

HBM_BYTES_PER_S = 819e9         # benchmark/peaks.json, TPU v5 lite
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "chiprun_out", "tune_qk_heads.txt")
#        rows, heads, D, head norm, positions given, theta
CELLS = {"sdar_q": (16384, 32, 128, True, True, 1e6),
         "sdar_k": (16384, 4, 128, True, True, 1e6),
         "st_q": (16384, 28, 128, False, False, 1.5e6),
         "st_k": (16384, 4, 128, False, False, 1.5e6)}


def say(line):
    """To standard output and, whole, to ``chiprun_out/``: a call returns
    only the end of what it printed."""
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(fn, args, iters):
    """(device ms a call of the jitted ``fn(*args)``, its result)."""
    out = jax.block_until_ready(fn(*args))
    if not iters:       # the rehearsal
        return float("nan"), out
    from benchmark import reduce_trace
    where = tempfile.mkdtemp(prefix="tune_qk_heads.")
    try:
        jax.profiler.start_trace(where)
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        busy = reduce_trace.reduce_dir(where)["busy_s"]
    finally:
        shutil.rmtree(where, ignore_errors=True)
    return 1e3 * busy / iters, out


def gap(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def share(passes, nbytes, ms):
    return 100 * passes * nbytes / HBM_BYTES_PER_S / (1e-3 * ms)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--tiles", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    say(f"device {device.platform} {device.device_kind}")
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit("needs a TPU")
    iters = 0 if args.rehearse else args.iters
    interpret = pallas.interpret_mode()
    for cell in args.cells.split(","):
        s, heads, d, normed, positioned, theta = CELLS[cell]
        if args.rehearse:
            s = 256
        k = jax.random.split(jax.random.key(0), 3)
        x = jax.random.normal(k[0], (1, s, heads * d), jnp.bfloat16)
        g = jax.random.normal(k[1], (1, heads, s, d), jnp.bfloat16)
        w = 1 + 0.1 * jax.random.normal(k[2], (d,), jnp.float32)
        at = jnp.concatenate([jnp.arange(s // 2, dtype=jnp.int32)] * 2)
        rest = (w,) * normed + (at,) * positioned
        attrs = dict(heads=heads, epsilon=1e-6, normed=normed,
                     positioned=positioned,
                     freq=tuple(F._rotary_frequencies(d, theta,
                                                      "tune").tolist()))
        n = x.size * 2
        seen = {}
        for name, fn in (("chain", F._qk_heads),
                         ("kernels", kernels.qk_heads)):
            def both(g, x, *rest, fn=fn):   # the result and every gradient
                y, vjp = jax.vjp(lambda *a: fn(*a, **attrs), x, *rest)
                return (y,) + vjp(g)[:1 + normed]

            ms_f, _ = timed(jax.jit(lambda x, *rest, fn=fn: fn(
                x, *rest, **attrs)), (x,) + rest, iters)
            ms_b, seen[name] = timed(jax.jit(both), (g, x) + rest, iters)
            say(f"{cell:7s} {name:8s} fwd {ms_f:7.3f} ms "
                f"({share(2, n, ms_f):5.1f} % of HBM peak at 2 n)  "
                f"fwd+bwd {ms_b:7.3f} ms ({share(5, n, ms_b):5.1f} % at "
                f"5 n)")
        say(f"{cell:7s} kernels against chain, largest gap / largest "
            f"value: " + " ".join(
                f"{what} {gap(a, b):.2e}" for what, a, b in zip(
                    ("y", "dx", "dw"), seen["kernels"], seen["chain"])))
        cos, sin = kernels.tables(at if positioned else None, s,
                                  attrs["freq"])
        w1 = w[None] if normed else None
        for tile in (t for t in args.tiles.split(",") if t):
            ts, hb = (int(v) for v in tile.split("x"))
            if s % ts or heads % hb:
                continue
            try:
                ms_f, _ = timed(lambda *a: kernels._forward(
                    *a, heads=heads, epsilon=1e-6, interpret=interpret,
                    tiles=(ts, hb)), (x, w1, cos, sin), iters)
                ms_b, _ = timed(lambda *a: kernels._backward(
                    *a, epsilon=1e-6, interpret=interpret, tiles=(ts, hb)),
                    (g, x, w1, cos, sin), iters)
            except Exception as e:      # a tile VMEM does not hold
                say(f"{cell:7s} tile {ts:4d} rows x {hb:2d} heads: "
                    f"{str(e).splitlines()[0][:120]}")
                continue
            say(f"{cell:7s} tile {ts:4d} rows x {hb:2d} heads: qk_heads_fwd "
                f"{ms_f:7.3f} ms ({share(2, n, ms_f):5.1f} %)  qk_heads_bwd "
                f"{ms_b:7.3f} ms ({share(3, n, ms_b):5.1f} % at 3 n)")


if __name__ == "__main__":
    main()
