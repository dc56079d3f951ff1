"""Flash-attention block sweep on the chip, at the geometries of the seven
benchmark cells that run the kernels (q/k and v head sizes apart):

    joyai     1 x 32 x 8192 x 192 | 128, causal
    nemotron  1 x 32 x 8192 x 128 | 128, causal
    seq512    16 x 12 x 512 x 64 | 64, key bias, no causal mask
    sdar      1 x 32 x (2 x 8192) x 128 | 128, block-diffusion structure,
              diffusion blocks of 4
    st_global 1 x 28 x 16384 x 128 | 128, causal (smallthinker's global
              layers)
    st_window the same under a sliding window of 4,096 (its window layers;
              the parent has no such call and is left out)
    lfm2      2 x 32 x 8192 x 64 | 64, causal (lfm2_8b_a1b's attention
              layer: two sequences a step, head size 64)
    phi4_causal 1 x 40 x 8192 x 64 | 128, causal (phi4_mini_flash's full and
                cross attention: differential attention's paired heads)
    phi4_window the same under a sliding window of 512 (its window layer: a
                window narrower than the default k-block)
    (``--cells phi4`` is both)

For each (block_q, block_k): forward ms, backward ms (the backward call
alone, on the forward's saved results) and forward + backward ms (host
clock around ``block_until_ready``, the kernels alone: no projections), the
forward's tiles and how many of them run the masked body. With
``--parent DIR`` (a checkout of the parent commit, e.g. ``git archive`` into
the git-ignored ``.benchmark_work/``) every row is timed on the parent's
kernels too, in the same process, and at the blocks the rule gives the
parent's and the change's o, dq, dk, dv are compared bit for bit.

    chiprun -- python -u scripts/tune_flash.py --parent .benchmark_work/parent

The table goes into PERF.md section 7, row 29.
"""
import argparse
import importlib
import importlib.util
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

GEOMETRIES = {
    # name: (batch, heads, seq, d, dv, causal, key_bias, diffusion block,
    # block pairs); under a diffusion block ``seq`` is two copies' rows
    "joyai": (1, 32, 8192, 192, 128, True, False, None,
              [(256, 256), (128, 512), (256, 512), (512, 512), (256, 1024),
               (512, 1024)]),
    "nemotron": (1, 32, 8192, 128, 128, True, False, None,
                 [(256, 256), (256, 512), (512, 512), (512, 1024),
                  (1024, 512), (1024, 1024)]),
    "seq512": (16, 12, 512, 64, 64, False, True, None,
               [(512, 512), (256, 512), (512, 256), (256, 256)]),
    "sdar": (1, 32, 16384, 128, 128, False, False, 4,
             [(256, 512), (512, 256), (512, 512)]),
    "st_global": (1, 28, 16384, 128, 128, True, False, None,
                  [(512, 512), (256, 512)]),
    "st_window": (1, 28, 16384, 128, 128, True, False, None,
                  [(512, 512), (256, 512), (512, 256), (256, 256)]),
    "lfm2": (2, 32, 8192, 64, 64, True, False, None,
             [(512, 1024), (512, 512), (256, 1024), (1024, 1024),
              (1024, 512), (256, 512)]),
    "phi4_causal": (1, 40, 8192, 64, 128, True, False, None,
                    [(512, 1024), (512, 512), (1024, 1024), (256, 1024),
                     (1024, 512)]),
    "phi4_window": (1, 40, 8192, 64, 128, True, False, None,
                    [(512, 1024), (512, 512), (256, 512), (512, 256),
                     (256, 256), (1024, 512)]),
}
# geometry -> flash_attention(window=)
WINDOWS = {"st_window": 4096, "phi4_window": 512}
ALIASES = {"phi4": ["phi4_causal", "phi4_window"]}


def load_kernels(parent):
    """The flash module of this checkout, or of the checkout at ``parent``
    loaded beside it (its relative imports resolve in this package)."""
    name = "paddle_tpu.ops.pallas.flash_attention"
    if parent is None:
        return importlib.import_module(name)
    path = os.path.join(parent, "paddle_tpu", "ops", "pallas",
                        "flash_attention.py")
    spec = importlib.util.spec_from_file_location(name + "_parent", path)
    module = importlib.util.module_from_spec(spec)
    module.__package__ = "paddle_tpu.ops.pallas"
    spec.loader.exec_module(module)
    return module


def make_inputs(geometry, seed=0):
    import jax.numpy as jnp
    b, h, s, d, dv, causal, key_bias, block, _ = geometry
    rng = np.random.RandomState(seed)
    q, k = (jnp.asarray(rng.randn(b, h, s, d), jnp.bfloat16)
            for _ in range(2))
    v, ct = (jnp.asarray(rng.randn(b, h, s, dv), jnp.bfloat16)
             for _ in range(2))
    bias = None
    if key_bias:
        bias = jnp.asarray(np.where(rng.rand(b, 1, 1, s) < 0.1, -1e9, 0.0),
                           jnp.float32)
    return q, k, v, ct, bias


def functions(module, geometry, bias, block_q, block_k, window=None):
    """(forward, backward on the forward's saved results, forward +
    backward) of ``module``'s kernels, jitted. ``backward(q, k, v, ct)``
    runs the forward once, outside the clock."""
    import jax
    import jax.numpy as jnp
    b, h, s, d, dv, causal, key_bias, block, _ = geometry
    mode = "key" if key_bias else None
    seed = jnp.zeros((2,), jnp.int32)

    if block:
        shift = block.bit_length() - 1

        def fwd(q, k, v):
            return module._flash_bd(q, k, v, shift, None, block_q, block_k)

        def saved(q, k, v):
            return module._bd_fwd_res(q, k, v, shift, None, block_q, block_k)

        def bwd(q, k, v, ct, out, mrow, lrow):
            return module._bd_bwd(q, k, v, out, mrow, lrow, ct, shift, None,
                                  block_q, block_k)
    elif window:
        def fwd(q, k, v):
            return module._flash_win(q, k, v, window, None, block_q, block_k)

        def saved(q, k, v):
            return module._win_fwd(q, k, v, window, None, block_q, block_k,
                                   False)

        def bwd(q, k, v, ct, out, mrow, lrow):
            return module._win_bwd(q, k, v, out, mrow, lrow, ct, window,
                                   None, block_q, block_k, False)
    else:
        def fwd(q, k, v):
            return module._flash(q, k, v, bias, mode, seed, causal, None,
                                 block_q, block_k, 0.0)

        def saved(q, k, v):
            return module._flash_fwd_res(q, k, v, bias, mode, seed, causal,
                                         None, block_q, block_k, 0.0)

        def bwd(q, k, v, ct, out, mrow, lrow):
            return module._flash_bwd(q, k, v, bias, mode, seed, out, mrow,
                                     lrow, ct, causal, None, block_q,
                                     block_k, 0.0)

    def both(q, k, v, ct):
        out, vjp = jax.vjp(fwd, q, k, v)
        return (out,) + vjp(ct)

    saved, bwd = jax.jit(saved), jax.jit(bwd)
    residuals = {}

    def backward(q, k, v, ct):
        if not residuals:
            residuals["r"] = jax.block_until_ready(saved(q, k, v))
        return bwd(q, k, v, ct, *residuals["r"])

    return jax.jit(fwd), backward, jax.jit(both)


def timed(fn, args, steps):
    import jax
    jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3


def ulps(a, b):
    """(share of elements that differ, the largest difference in units in
    the last bfloat16 place of the larger of the two elements, the largest
    difference over the largest element of ``a``)."""
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    differ = a != b
    if not differ.any():
        return 0.0, 0.0, 0.0
    size = np.maximum(np.abs(a), np.abs(b))[differ]
    spacing = 2.0 ** (np.floor(np.log2(size)) - 7)
    gap = np.abs(a - b)[differ]
    return (float(differ.mean()), float((gap / spacing).max()),
            float(gap.max() / np.abs(a).max()))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", default=None)
    ap.add_argument("--cells", default="joyai,nemotron,seq512,sdar,"
                                       "st_global,st_window,lfm2")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    import jax
    print("device", jax.devices()[0].device_kind, flush=True)
    change = load_kernels(None)
    all_sides = [("change", change)]
    if args.parent:
        all_sides.insert(0, ("parent", load_kernels(args.parent)))

    for cell in [c for name in args.cells.split(",")
                 for c in ALIASES.get(name, [name])]:
        geometry, window = GEOMETRIES[cell], WINDOWS.get(cell)
        sides = [side for side in all_sides
                 if window is None or hasattr(side[1], "_flash_win")]
        b, h, s, d, dv, causal, key_bias, block, pairs = geometry
        q, k, v, ct, bias = make_inputs(geometry)
        held = s // 2 if block else s
        rule = {name: m._blocks_that_fit(held, d, dv, 2, 512, 1024)
                for name, m in sides}
        if hasattr(change, "_window_blocks"):   # the window's clause
            rule["change"] = change._window_blocks(window, *rule["change"])
        vmem = change._bwd_params(
            held, d, dv, 2, *rule["change"],
            change._single_buffered(held, d, dv, 2)).vmem_limit_bytes
        print(f"{cell}: a side {change._side_bytes(held, d, dv, 2) / 2**20:.1f}"
              f" MiB, the backward call may use {vmem / 2**20:.1f} MiB of "
              f"VMEM at the rule's blocks", flush=True)
        print(f"{cell}: {b} x {h} x {s} x {d} | {dv} causal={causal} "
              f"key_bias={key_bias} window={window} rule={rule}", flush=True)
        for bq, bk in pairs:
            if block:
                shift = block.bit_length() - 1
                bq_, bk_ = change._bd_blocks(bq, bk, held, shift)
                tiles, masked, _ = change._bd_tile_counts(
                    b * h, held, block_q=bq_, block_k=bk_, shift=shift)
            else:
                bq_, bk_ = change._clamped_blocks(bq, bk, s, s)
                tiles, masked = change._tile_counts(
                    b * h, block_q=bq_, block_k=bk_, sq=s, sk=s,
                    causal=causal, window=window)
            row = f"  {bq:4d} x {bk:4d}  tiles {tiles:6d} masked {masked:5d}"
            for name, module in sides:
                try:
                    f, back, fb = functions(module, geometry, bias, bq, bk,
                                            window)
                    ms = timed(f, (q, k, v), args.steps)
                    ms_back = timed(back, (q, k, v, ct), args.steps)
                    ms_both = timed(fb, (q, k, v, ct), args.steps)
                    row += (f"  {name} fwd {ms:7.3f} bwd {ms_back:7.3f} "
                            f"fwd+bwd {ms_both:7.3f}")
                except Exception as e:     # noqa: BLE001 - VMEM, mostly
                    row += f"  {name} FAIL {type(e).__name__}: " \
                           f"{str(e).splitlines()[0][:120]}"
            print(row, flush=True)
        if len(sides) == 2:
            # the same inputs through both sides, each at its rule's blocks
            outs = {name: functions(m, geometry, bias, *rule[name])[2](
                q, k, v, ct)
                    for name, m in sides}
            for label, a, c in zip(("o", "dq", "dk", "dv"), outs["parent"],
                                   outs["change"]):
                share, worst, rel = ulps(a, c)
                print(f"  parent vs change {label}: {share:.6f} of elements "
                      f"differ, at most {worst:.2f} bf16 ulp of the element, "
                      f"{rel:.2e} of the largest element", flush=True)


if __name__ == "__main__":
    main()
