"""Time the three ops of a learned sparse attention alone at the
``keye_vl2_30b_a3b`` cell's shape (1 x 16,384 rows; 32 heads of 128 after
the K/V heads are repeated; an indexer of 16 heads of 64; 2,048 keys a
row; bfloat16), each kernel against what it is compared with:

    chiprun -- python3 scripts/tune_dsa.py [--iters 5] [--skip-xla] \
        [--select-rows 128,256] [--select-chunks 256,512,1024]

* ``F.dsa_select``: the kernel ``dsa_select`` (``ops/pallas/dsa.py``)
  against the definition route (a sort a block of rows), and the kernel
  at other rows a program / keys a chunk; the kernel's first result is the
  PACKED selection, so the int8 array's unpacking is timed beside it, as
  the forward has it (row-major) and as a recomputed block's replay has it
  (transposed for ``flash_sel_bwd``), against the transpose of the int8
  array that the replay made before;
* attention under the selection: ``flash_sel_fwd`` / ``flash_sel_bwd``
  against the dense causal call's ``flash_fwd`` / ``flash_bwd`` at the
  same 32 heads (the masked walk's price over the dense walk), forward
  alone and forward + backward;
* ``F.dsa_indexer_loss``: the kernel ``dsa_kl`` against the definition
  route (row blocks in XLA).

Prints ms a call (host clock around ``block_until_ready`` of ``--iters``
calls after two warm ones: every call is tens of milliseconds, far more
than the host needs to send one), each kernel's share of its roofline by
``benchmark/keye_vl_costs.py``, and the selected share of the causal
pairs. The lines also go to ``chiprun_out/tune_dsa.txt``. One process, one
chip. Needs a TPU: a CPU number is no device number (``--rehearse`` walks
it tiny, interpreted, and prints no time).
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
import numpy as np              # noqa: E402

from benchmark import keye_vl_costs, kernel_costs           # noqa: E402
from paddle_tpu.ops import sparse_attention as sa            # noqa: E402
from paddle_tpu.ops.pallas import dsa                        # noqa: E402
from paddle_tpu.ops.pallas import flash_attention_mod as fa  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out", "tune_dsa.txt")


def say(line):
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def timed(fn, args, iters):
    """ms a call, or None in a rehearsal (``iters`` 0: one call, no time)."""
    out = jax.block_until_ready(fn(*args))
    if not iters:
        return None, out
    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / iters, out


def line(name, ms, costs=None, peaks=None):
    if ms is None:
        return say(f"{name}: ran")
    note = ""
    if costs is not None:
        share, bound = kernel_costs.roofline_share_pct(*costs, 1e-3 * ms,
                                                       peaks)
        note = f"  {share:.1f} % of its roofline ({bound})"
    say(f"{name}: {ms:.2f} ms a call{note}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--skip-xla", action="store_true")
    ap.add_argument("--select-rows", default="")
    ap.add_argument("--select-chunks", default="")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye_vl2_30b_a3b.json")) as f:
        cfg = json.load(f)
    s, h, d, hi, di, top_k = 16384, 32, 128, 16, 64, 2048
    dtype, iters = jnp.bfloat16, args.iters
    peaks = None
    if args.rehearse:
        s, h, d, hi, di, top_k, dtype, iters = 1024, 4, 128, 4, 8, 32, \
            jnp.float32, 0
        cfg = dict(cfg, num_attention_heads=h, num_key_value_heads=h,
                   head_dim=d, sa_config=dict(
                       cfg["sa_config"], indexer_num_heads=hi,
                       indexer_head_dim=di, topk=top_k))
    else:
        device = jax.devices()[0]
        if device.platform != "tpu":
            raise SystemExit("tune_dsa needs a TPU (or --rehearse)")
        peaks = kernel_costs.peaks_for_kind(device.device_kind)
    key = jax.random.key(47)
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i), (1, h, s, d),
                                    jnp.float32).astype(dtype)
                  for i in range(4))
    qi = jax.random.normal(jax.random.fold_in(key, 5), (1, hi, s, di),
                           jnp.float32).astype(dtype)
    ki = jax.random.normal(jax.random.fold_in(key, 6), (1, s, di),
                           jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 7), (1, s, hi),
                          jnp.float32) / np.sqrt(hi * di)
    say(f"# 1 x {s} rows, {h} heads of {d}, indexer {hi} x {di}, top_k "
        f"{top_k}, {jnp.dtype(dtype).name}, {iters} calls")
    rows, chunk = (dsa.SELECT_ROWS, dsa.SELECT_CHUNK) if not args.rehearse \
        else (32, 128)

    # -- the selection
    select = jax.jit(lambda *a: dsa.select(*a, top_k=top_k, rows=rows,
                                           chunk=chunk))
    ms, (bits, lse, _, pairs) = timed(select, (qi, ki, w), iters)
    line(f"dsa_select kernel {rows} x {chunk}", ms,
         keye_vl_costs.select_kernel_costs(cfg, s), peaks)
    unpack = lambda bits: sa._unpack(bits, s)
    ms, sel = timed(jax.jit(unpack), (bits,), iters)
    line("  its bits unpacked to int8 [S, S]", ms)
    ms, _ = timed(jax.jit(lambda bits: jnp.swapaxes(unpack(bits), 2, 3)),
                  (bits,), iters)
    line("  its bits unpacked to the transposed int8 [S, S]", ms)
    ms, _ = timed(jax.jit(lambda sel: jnp.swapaxes(sel, 2, 3)), (sel,),
                  iters)
    line("  the int8 [S, S] transposed", ms)
    say(f"  selected {int(pairs[0])} of {s * (s + 1) // 2} causal pairs "
        f"({200.0 * int(pairs[0]) / (s * (s + 1)):.2f} %)")
    for r in [int(x) for x in args.select_rows.split(",") if x]:
        for c in [int(x) for x in args.select_chunks.split(",") if x]:
            if (r, c) != (rows, chunk) and dsa.select_supported(
                    qi.shape, r, c):
                ms, _ = timed(jax.jit(lambda *a, r=r, c=c: dsa.select(
                    *a, top_k=top_k, rows=r, chunk=c)), (qi, ki, w), iters)
                line(f"dsa_select kernel {r} x {c}", ms)
    if not args.skip_xla:
        ms, out = timed(jax.jit(lambda *a: sa._select(*a, top_k=top_k)),
                        (qi, ki, w), iters)
        line("dsa_select definition route (a sort a row block)", ms)
        say(f"  its selection differs in "
            f"{int(jnp.sum(unpack(out[0]) != sel))} pairs")

    # -- attention under it, against the dense causal call
    block_q, block_k = fa._blocks_that_fit(s, d, d, q.dtype.itemsize, 512,
                                           1024)
    if args.rehearse:
        block_q, block_k = 32, 64
    seed = jnp.zeros((2,), jnp.int32)

    def sparse(q, k, v):
        return fa._flash_sel(q, k, v, sel, None, block_q, block_k)[0]

    def dense(q, k, v):
        return fa._flash(q, k, v, None, None, seed, True, None, block_q,
                         block_k, 0.0)

    flops, nbytes = keye_vl_costs.selected_flash_costs(cfg, s)
    for name, fn in (("selected", sparse), ("dense causal", dense)):
        fwd, _ = timed(jax.jit(fn), (q, k, v), iters)
        both, _ = timed(jax.jit(lambda q, k, v, fn=fn: jax.vjp(
            fn, q, k, v)[1](g)), (q, k, v), iters)
        line(f"flash {name} forward", fwd)
        line(f"flash {name} forward + backward", both,
             (flops, nbytes) if name == "selected" else None, peaks)

    # -- the indexer's loss
    _, m, l = jax.jit(lambda q, k, v: fa._flash_sel(
        q, k, v, sel, None, block_q, block_k))(q, k, v)
    operands = (q, k, m, l, sel, qi, ki, w, lse)
    ms, got = timed(jax.jit(lambda *a: dsa.kl_and_grads(
        *a, None, **({"block": 64} if args.rehearse else {}))), operands,
        iters)
    line("dsa_kl kernel", ms, keye_vl_costs.kl_kernel_costs(cfg, s), peaks)
    if not args.skip_xla:
        ms, want = timed(jax.jit(lambda *a: sa._kl_and_grads(*a, None)),
                         operands, iters)
        line("dsa_indexer_loss definition route (row blocks)", ms)
        say("  kernel against it: loss %.6f / %.6f, largest gradient gap "
            "%.3g" % (float(got[0]), float(want[0]), max(
                float(jnp.abs(a.astype(jnp.float32)
                              - b.astype(jnp.float32)).max())
                for a, b in zip(got[1:], want[1:]))))


if __name__ == "__main__":
    main()
