"""Time ``F.mla_heads`` alone at the joyai cell's shape, the kernel pair of
``ops/pallas/mla_heads.py`` against the composition
(``ops/nn_ops.py: _mla_heads``: the transposes, slices, interleaved
rotations, the key head's broadcast and the two concatenations that
``MultiHeadLatentAttention.qkv`` wrote down before PR 48):

    chiprun -- python3 scripts/tune_mla_heads.py [--iters 10] \
        [--cells joyai] [--rows 1024,512,256]

``joyai``: 1 x 8,192 rows x 32 heads of 128 + 64 (q) and 128 + 128 (k_nope
and v), one rotary key head of 64, bfloat16, theta 32e6. Prints ms a forward
call and ms a forward + backward call of each path, the share of the HBM
peak that one read of the three inputs and one write of the three results
are of each (forward: 504 MB; forward + backward twice that; the 192-wide
heads counted 192 wide, though HBM stores them in 256 lanes), and the
largest difference of the kernels' results from the composition's.
``--rows`` times the two kernels alone at each row tile (``_forward`` /
``_backward`` called directly); a program is always one PAIR of heads, 384
lanes of q, which makes every block whole lane tiles though every second
head starts mid-tile; and, forward alone, the other candidate layout:
the projections split by columns of their weights in front of the kernel
(``split_forward`` below), every block starting on a lane tile.
A call takes under a millisecond, less than the host needs to send one, so
the times are the DEVICE's: a profiler trace of ``--iters`` calls, reduced
as the benchmark reduces its own (``benchmark/reduce_trace.py``), the
device's busy time a call -- every instruction of the call's program, the
table's cosines and sines included; beside each row tile's forward stands
the host's clock around 40 queued calls, a check on that reading (at PR
48 both read a call alone at twice its time inside the step, PERF.md
section 7 row 50: this table compares layouts and tiles, a traced step
gives the rate). The lines also go to ``chiprun_out/tune_mla_heads.txt``. One process, one chip. Needs a TPU: a
CPU number is no device number (``--rehearse`` walks it tiny, interpreted,
and prints no time).
"""
import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
from jax.experimental import pallas as pl               # noqa: E402

from paddle_tpu.ops import nn_ops as F                      # noqa: E402
from paddle_tpu.ops import pallas                           # noqa: E402
from paddle_tpu.ops.pallas import mla_heads as kernels      # noqa: E402
from scripts import tune_qk_heads as base                   # noqa: E402

base.OUT = os.path.join(os.path.dirname(base.OUT), "tune_mla_heads.txt")
say, timed, gap, share = base.say, base.timed, base.gap, base.share
#        rows, heads, nope, v, theta
CELLS = {"joyai": (8192, 32, 128, 128, 32e6)}
ROPE = kernels.ROPE


def split_forward(q_nope, q_rope, k_nope, val, kr, table, m, *, heads,
                  interpret, rows):
    """The other candidate layout, forward alone: the two projections
    split by COLUMNS of their weights in front of the kernel (``q_nope``
    [B S, H 128], ``q_rope`` [B S, H 64], ``k_nope`` and ``val`` [B S, H
    128]), so that every block a program reads starts on a lane tile and
    two heads' rotary parts share one. Same results as ``_forward``."""
    bs, nope = q_nope.shape[0], q_nope.shape[1] // heads
    v = val.shape[1] // heads
    vmem = kernels._vmem

    def body(qn_ref, qr_ref, kn_ref, v_ref, kr_ref, table_ref, m_ref,
             q_out, k_out, v_out):
        m, table = m_ref[...], table_ref[...]
        k_rope = kernels._turn(kr_ref[...], m, table).astype(q_out.dtype)
        for h in range(2):
            q_out[0, h, :, :nope] = qn_ref[:, h * nope:(h + 1) * nope]
            q_out[0, h, :, nope:] = kernels._turn(
                qr_ref[:, h * ROPE:(h + 1) * ROPE], m,
                table).astype(q_out.dtype)
            k_out[0, h, :, :nope] = kn_ref[:, h * nope:(h + 1) * nope]
            k_out[0, h, :, nope:] = k_rope
            v_out[0, h] = v_ref[:, h * v:(h + 1) * v]

    flat = lambda d: vmem((rows, 2 * d), lambda b, k, j: (k, j))
    head = lambda d: vmem((1, 2, rows, d), lambda b, k, j: (0, j, k, 0))
    shape = lambda d: jax.ShapeDtypeStruct((1, heads, bs, d), q_nope.dtype)
    return pl.pallas_call(
        body, grid=(1, bs // rows, heads // 2),
        in_specs=[flat(nope), flat(ROPE), flat(nope), flat(v),
                  vmem((rows, ROPE), lambda b, k, j: (k, 0)),
                  vmem((rows, 128), lambda b, k, j: (k, 0)),
                  vmem((ROPE, 128), lambda b, k, j: (0, 0))],
        out_specs=[head(nope + ROPE), head(nope + ROPE), head(v)],
        out_shape=[shape(nope + ROPE), shape(nope + ROPE), shape(v)],
        compiler_params=kernels._specs(1, bs, heads, nope, v,
                                       q_nope.dtype.itemsize, rows)[2],
        interpret=interpret, name="mla_heads_split_fwd",
    )(q_nope, q_rope, k_nope, val, kr, table, m)


def wall(fn, args, calls=40):
    """ms a call by the host's clock around ``calls`` queued calls: a check
    on the trace's reading (a call is longer than its dispatch)."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / calls


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--cells", default=",".join(CELLS))
    ap.add_argument("--rows", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    device = jax.devices()[0]
    say(f"device {device.platform} {device.device_kind}")
    if device.platform != "tpu" and not args.rehearse:
        raise SystemExit("needs a TPU")
    iters = 0 if args.rehearse else args.iters
    if args.rehearse:
        base.OUT = os.devnull
    interpret = pallas.interpret_mode()
    for cell in args.cells.split(","):
        s, heads, nope, v, theta = CELLS[cell]
        if args.rehearse:
            s, heads = 256, 4
        k = jax.random.split(jax.random.key(0), 6)
        widths = (heads * (nope + ROPE), heads * (nope + v), ROPE)
        xs = tuple(jax.random.normal(ki, (1, s, n), jnp.bfloat16)
                   for ki, n in zip(k, widths))
        gs = tuple(jax.random.normal(ki, (1, heads, s, d), jnp.bfloat16)
                   for ki, d in zip(k[3:], (nope + ROPE, nope + ROPE, v)))
        attrs = dict(heads=heads, nope=nope, v=v, freq=tuple(
            F._rotary_frequencies(ROPE, theta, "tune").tolist()))
        assert kernels.supported(*(x.shape for x in xs), heads, nope, v,
                                 [x.dtype for x in xs])
        n = 2 * (sum(x.size for x in xs) + sum(g.size for g in gs))
        seen = {}
        for name, fn in (("chain", F._mla_heads),
                         ("kernels", kernels.mla_heads)):
            def both(gs, *xs, fn=fn):   # the results and every gradient
                y, vjp = jax.vjp(lambda *a: fn(*a, **attrs), *xs)
                return tuple(y) + vjp(tuple(gs))

            ms_f, _ = timed(jax.jit(lambda *xs, fn=fn: fn(*xs, **attrs)),
                            xs, iters)
            ms_b, seen[name] = timed(jax.jit(both), (gs,) + xs, iters)
            say(f"{cell:6s} {name:8s} fwd {ms_f:7.3f} ms "
                f"({share(1, n, ms_f):5.1f} % of HBM peak at n = "
                f"{n / 1e6:.0f} MB)  fwd+bwd {ms_b:7.3f} ms "
                f"({share(2, n, ms_b):5.1f} % at 2 n)")
        say(f"{cell:6s} kernels against chain, largest gap / largest "
            f"value: " + " ".join(
                f"{what} {gap(a, b):.2e}" for what, a, b in zip(
                    ("q", "k", "v", "dq", "dkv", "dk_rope"),
                    seen["kernels"], seen["chain"])))
        fwd = kernels.operands(s, attrs["freq"], jnp.bfloat16)
        bwd = kernels.operands(s, attrs["freq"], jnp.bfloat16, backward=True)
        for rows in (int(r) for r in args.rows.split(",") if r):
            if s % rows:
                continue
            try:
                ms_f, _ = timed(lambda *a: kernels._forward(
                    *a, heads=heads, nope=nope, v=v, interpret=interpret,
                    rows=rows), xs + fwd, iters)
                ms_b, _ = timed(lambda *a: kernels._backward(
                    *a, interpret=interpret, rows=rows),
                    gs + bwd, iters)
            except Exception as e:      # a tile VMEM does not hold
                say(f"{cell:6s} {rows:4d} rows: "
                    f"{str(e).splitlines()[0][:120]}")
                continue
            say(f"{cell:6s} {rows:4d} rows x 2 heads: mla_heads_fwd "
                f"{ms_f:7.3f} ms ({share(1, n, ms_f):5.1f} %)  "
                f"mla_heads_bwd {ms_b:7.3f} ms ({share(1, n, ms_b):5.1f} %)")
            if iters:
                ms = wall(lambda *a: kernels._forward(
                    *a, heads=heads, nope=nope, v=v, interpret=interpret,
                    rows=rows), xs + fwd)
                say(f"{cell:6s} {rows:4d} rows x 2 heads: mla_heads_fwd by "
                    f"the host's clock around 40 calls {ms:7.3f} ms")
        # the columns of each projection apart, as two products would
        # write them (here: slices of the same values, made outside the
        # timed call)
        by_head = [x.reshape(s, heads, -1) for x in xs[:2]]
        parts = tuple(t.reshape(s, -1) for t in (
            by_head[0][..., :nope], by_head[0][..., nope:],
            by_head[1][..., :nope], by_head[1][..., nope:]))
        parts += (xs[2].reshape(s, ROPE),) + fwd
        want = kernels._forward(*xs, *fwd, heads=heads, nope=nope, v=v,
                                interpret=interpret)
        for rows in (int(r) for r in args.rows.split(",") if r):
            if s % rows:
                continue
            ms_f, got = timed(jax.jit(functools.partial(
                split_forward, heads=heads, interpret=interpret, rows=rows)),
                parts, iters)
            say(f"{cell:6s} {rows:4d} rows x 2 heads, projections split by "
                f"columns: forward {ms_f:7.3f} ms ({share(1, n, ms_f):5.1f} "
                f"%), gap " + " ".join(f"{gap(a, b):.1e}"
                                       for a, b in zip(got, want)))


if __name__ == "__main__":
    main()
