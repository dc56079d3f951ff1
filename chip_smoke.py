"""chip_smoke.py — does the main path still start on the chip?

    python chip_smoke.py             # one TPU chip: device, kernels, train, serve
    python chip_smoke.py --chips 4   # four chips: the fleet (dp2 x tp2) phase only

One process, the public API, seeded random weights, no network. Every
phase prints labelled lines as it goes and raises on the first thing
that is wrong; nothing is caught, so a failed phase ends the run with a
non-zero exit code. The last line of a passing run is one JSON object,
``{"ok": true, "device": {...}}``.

Each phase is a function of its sizes (``tests/test_chip_smoke.py`` runs
them tiny on the CPU); ``main()`` alone checks the device and fixes the
sizes. Times printed here are a builder's note (host clock around
``block_until_ready``, warm), not a benchmark result.
"""
import argparse
import json
import os
import time

import numpy as np


def say(phase, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _err(got, want):
    """Largest absolute difference, in units of the reference's largest
    magnitude — one number that reads the same for bf16 and f32."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-6))


def _raw(x):
    return getattr(x, "data", x)     # framework ops may wrap a Tensor


def _custom_calls(compiled):
    return compiled.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# device


def phase_device(want_count):
    import importlib.metadata as md
    import jax
    import jaxlib
    import paddle_tpu as pt
    from paddle_tpu.io import native
    from paddle_tpu.monitor.step import ceilings_for_kind

    devs = jax.devices()
    d = devs[0]
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devs), jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=md.version("libtpu"))
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{d.platform!r} ({d.device_kind!r})")
    if len(devs) < want_count:
        raise SystemExit(f"chip_smoke was asked for {want_count} chips "
                         f"and JAX found {len(devs)}")
    flops, hbm = ceilings_for_kind(d.device_kind)
    if flops is None or hbm is None:
        raise SystemExit(f"device kind {d.device_kind!r} is not in the "
                         f"peaks table (paddle_tpu/monitor/step.py)")
    say("device", peak_bf16_flops=flops, peak_hbm_bytes_per_s=hbm)
    say("device", compile_cache=pt.enable_compilation_cache())
    prebuilt = os.path.exists(native._LIB_PATH)
    native.get_lib()
    say("device", native_host_runtime="found prebuilt" if prebuilt
        else "built now from csrc/core.cpp")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# kernels


def phase_kernels(rows, hidden, batch, heads, seq, head_dim,
                  tol_f32=1e-4, tol_bf16=3e-2):
    """Every kernel the defaults ship on, compiled, fwd+bwd at the given
    shapes, against the repo's plain jax.numpy path evaluated in f32."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops import pallas as P
    from paddle_tpu.ops.pallas.flash_attention import (_mask_mode,
                                                       flash_attention)
    from paddle_tpu.ops.pallas.layer_norm import layer_norm as pallas_ln

    shipped = sorted(k for k, on in P._AUTO_ON.items() if on)
    if shipped != ["causal_conv1d", "dsa_kl", "dsa_select",
                   "flash_attention", "gated_rms_norm", "gated_short_conv",
                   "layer_norm", "mla_heads", "moe_grouped",
                   "moe_scatter_add", "qk_heads", "selective_scan",
                   "ssd_scan"]:
        raise AssertionError(f"_AUTO_ON ships {shipped}; this phase covers "
                             f"causal_conv1d, dsa_kl, dsa_select, "
                             f"flash_attention, gated_rms_norm, "
                             f"gated_short_conv, layer_norm, mla_heads, "
                             f"moe_grouped, moe_scatter_add, qk_heads, "
                             f"selective_scan and ssd_scan")
    on_chip = pt.device.is_tpu_backend()
    if on_chip and P.interpret_mode():
        raise AssertionError("interpret mode reachable on a TPU backend")
    rng = np.random.RandomState(0)

    def run(name, kernel_loss, ref_loss, args, n_diff, tol, min_calls):
        kfn = jax.jit(jax.value_and_grad(kernel_loss,
                                         argnums=tuple(range(n_diff))))
        compiled = kfn.lower(*args).compile()
        calls = _custom_calls(compiled)
        if on_chip and calls < min_calls:
            raise AssertionError(
                f"{name}: {calls} tpu_custom_call(s) in the compiled "
                f"program, expected >= {min_calls} — the kernel was not "
                f"compiled in")
        loss, grads = compiled(*args)
        f32 = [a.astype(jnp.float32) if jnp.issubdtype(a.dtype, jnp.floating)
               else a for a in args]
        rloss, rgrads = jax.jit(jax.value_and_grad(
            ref_loss, argnums=tuple(range(n_diff))))(*f32)
        errs = [_err(loss, rloss)] + [_err(g, r)
                                      for g, r in zip(grads, rgrads)]
        say("kernels", kernel=name, tpu_custom_calls=calls,
            max_err=f"{max(errs):.2e}", tol=tol)
        if max(errs) > tol:
            raise AssertionError(f"{name}: error {max(errs):.3e} > {tol}")

    # layer norm over (rows, hidden), f32 and bf16
    for dt, tol in ((jnp.float32, tol_f32), (jnp.bfloat16, tol_bf16)):
        x = jnp.asarray(rng.randn(rows, hidden), dt)
        w = jnp.asarray(1.0 + 0.1 * rng.randn(hidden), jnp.float32)
        b = jnp.asarray(0.1 * rng.randn(hidden), jnp.float32)
        ct = jnp.asarray(rng.randn(rows, hidden), jnp.float32)

        def k_ln(x, w, b, ct):
            return (_raw(pallas_ln(x, w, b, 1e-12)).astype(jnp.float32)
                    * ct).sum()

        def r_ln(x, w, b, ct):
            return (_raw(F.layer_norm(x, hidden, w, b, 1e-12)) * ct).sum()

        run(f"layer_norm[{rows}x{hidden},{jnp.dtype(dt).name}]",
            k_ln, r_ln, (x, w, b, ct), 3, tol, 2)

    # flash attention over (batch, heads, seq, head_dim) bf16: plain,
    # with BERT's additive [B,1,1,S] padding mask, and causal
    q, k, v = (jnp.asarray(rng.randn(batch, heads, seq, head_dim),
                           jnp.bfloat16) for _ in range(3))
    ct = jnp.asarray(rng.randn(batch, heads, seq, head_dim), jnp.float32)
    live = rng.randint(seq // 2, seq + 1, (batch,))
    pad = (np.arange(seq)[None, :] < live[:, None]).astype("f4")
    mask = jnp.asarray(((1.0 - pad) * -1e9)[:, None, None, :])
    if _mask_mode(mask.shape, batch, heads, seq, seq) == "fallback":
        raise AssertionError("BERT's padding mask would fall to sdpa")
    for name, m, causal in (("plain", None, False), ("padmask", mask, False),
                            ("causal", None, True)):
        def k_fa(q, k, v, ct, m=m, causal=causal):
            out = flash_attention(q, k, v, attn_mask=m, causal=causal,
                                  force=True)
            return (_raw(out).astype(jnp.float32) * ct).sum()

        def r_fa(q, k, v, ct, m=m, causal=causal):
            out = F.scaled_dot_product_attention(q, k, v, attn_mask=m,
                                                 is_causal=causal)
            return (_raw(out) * ct).sum()

        run(f"flash_attention[{batch}x{heads}x{seq}x{head_dim},bf16,"
            f"{name}]", k_fa, r_fa, (q, k, v, ct), 3, tol_bf16, 2)

    # latent attention's head sizes, causal: q and k 3/2 as wide as v (192
    # and 128 where head_dim is 64), against float32 sdpa — the number the
    # next change to the kernels' arithmetic has to hold (forward and the
    # one backward kernel: dq through its float32 accumulator)
    dq, dv = 3 * head_dim, 2 * head_dim
    q, k = (jnp.asarray(rng.randn(batch, heads, seq, dq), jnp.bfloat16)
            for _ in range(2))
    v = jnp.asarray(rng.randn(batch, heads, seq, dv), jnp.bfloat16)
    ct = jnp.asarray(rng.randn(batch, heads, seq, dv), jnp.float32)

    def k_mla(q, k, v, ct):
        out = flash_attention(q, k, v, causal=True, force=True)
        return (_raw(out).astype(jnp.float32) * ct).sum()

    def r_mla(q, k, v, ct):
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        return (_raw(out) * ct).sum()

    run(f"flash_attention[{batch}x{heads}x{seq}x{dq}|{dv},bf16,causal]",
        k_mla, r_mla, (q, k, v, ct), 3, tol_bf16, 2)

    # the block-diffusion structure over two copies of seq / 2 positions
    # in blocks of 4 (rows [0, seq / 2) the noisy copy), against float32
    # attention under the dense mask the kernels never build
    from paddle_tpu.ops.pallas.flash_attention import block_diffusion_mask
    q, k, v = (jnp.asarray(rng.randn(batch, heads, seq, 2 * head_dim),
                           jnp.bfloat16) for _ in range(3))
    ct = jnp.asarray(rng.randn(batch, heads, seq, 2 * head_dim), jnp.float32)
    dense = jnp.asarray(block_diffusion_mask(seq // 2, 4))

    def k_bd(q, k, v, ct):
        out = flash_attention(q, k, v, diffusion_block=4, force=True)
        return (_raw(out).astype(jnp.float32) * ct).sum()

    def r_bd(q, k, v, ct):
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=dense)
        return (_raw(out) * ct).sum()

    run(f"flash_attention[{batch}x{heads}x{seq}x{2 * head_dim},bf16,"
        f"block_diffusion]", k_bd, r_bd, (q, k, v, ct), 3, tol_bf16, 2)

    # a sliding window of a quarter of the sequence beside the causal
    # diagonal, against float32 attention under the dense window mask
    from paddle_tpu.ops.pallas.flash_attention import sliding_window_mask
    band = jnp.asarray(sliding_window_mask(seq, seq // 4))

    def k_win(q, k, v, ct):
        out = flash_attention(q, k, v, causal=True, window=seq // 4,
                              force=True)
        return (_raw(out).astype(jnp.float32) * ct).sum()

    def r_win(q, k, v, ct):
        out = F.scaled_dot_product_attention(q, k, v, attn_mask=band)
        return (_raw(out) * ct).sum()

    run(f"flash_attention[{batch}x{heads}x{seq}x{2 * head_dim},bf16,"
        f"window{seq // 4}]", k_win, r_win, (q, k, v, ct), 3, tol_bf16, 2)

    # the Mamba-2 scan over (batch, seq, 2 * heads heads of 64 in `heads`
    # groups, state 128), bf16 products, all seven gradients
    from paddle_tpu.ops.pallas import ssd_scan as ssd
    from paddle_tpu.ops.ssm import _ssd
    h, p, n, chunk = 2 * heads, 64, 128, 128
    if not ssd.supported((batch, seq, h, p), (batch, seq, heads, n), chunk):
        raise AssertionError("the scan's kernels would not take this shape")
    scan_args = (
        jnp.asarray(rng.randn(batch, seq, h, p), jnp.bfloat16),
        jnp.asarray(rng.randn(batch, seq, h), jnp.float32),
        jnp.asarray(np.log(rng.uniform(1.0, 16.0, h)), jnp.float32),
        jnp.asarray(rng.randn(batch, seq, heads, n), jnp.bfloat16),
        jnp.asarray(rng.randn(batch, seq, heads, n), jnp.bfloat16),
        jnp.asarray(rng.randn(h), jnp.float32),
        jnp.asarray(rng.randn(h) - 2.0, jnp.float32),
        jnp.asarray(rng.randn(batch, seq, h, p), jnp.float32))

    def k_scan(*a):
        y = ssd.ssd_scan(*a[:7], chunk=chunk, dot_dtype=jnp.bfloat16)
        return (y.astype(jnp.float32) * a[7]).sum()

    def r_scan(*a):
        return (_ssd(*a[:7], chunk=chunk, dot_dtype=jnp.float32) * a[7]).sum()

    run(f"ssd_scan[{batch}x{seq}x{h}x{p},state{n},bf16]", k_scan, r_scan,
        scan_args, 7, tol_bf16, 2)

    # Mamba-1's selective scan over (batch, seq, 1,024 channels, state 16):
    # a transition a (channel, state) pair, the positions walked on the
    # vector unit, all six gradients
    from paddle_tpu.ops.pallas import selective_scan as sscan
    from paddle_tpu.ops.ssm import _selective_scan
    wide, n1 = sscan.CHANNELS, 16
    if not sscan.supported((batch, seq, wide), n1):
        raise AssertionError("the selective scan's kernels would not take "
                             "this shape")
    own = np.random.RandomState(1)      # the checks below keep their draws
    sel_args = (
        jnp.asarray(own.randn(batch, seq, wide), jnp.bfloat16),
        jnp.asarray(np.exp(own.uniform(np.log(1e-3), np.log(1e-1),
                                       (batch, seq, wide))), jnp.float32),
        jnp.asarray(-own.uniform(1.0, 16.0, (wide, n1)), jnp.float32),
        jnp.asarray(own.randn(batch, seq, n1), jnp.bfloat16),
        jnp.asarray(own.randn(batch, seq, n1), jnp.bfloat16),
        jnp.asarray(own.randn(wide), jnp.float32),
        jnp.asarray(own.randn(batch, seq, wide), jnp.float32))
    run(f"selective_scan[{batch}x{seq}x{wide},state{n1},bf16]",
        lambda *a: (sscan.selective_scan(*a[:6]) * a[6]).sum(),
        lambda *a: (_selective_scan(*a[:6], chunk=sscan.CHUNK) * a[6]).sum(),
        sel_args, 6, tol_bf16, 2)

    # the same mixer's convolution (x | B | C: h * p + 2 * heads * n
    # channels, 4 taps, SiLU) and its gated norm (h * p lanes in `heads`
    # groups), bf16 in and out
    from paddle_tpu.ops.nn_ops import _rms_norm
    from paddle_tpu.ops.pallas import causal_conv1d as conv
    from paddle_tpu.ops.pallas import gated_rms_norm as gnorm
    from paddle_tpu.ops.ssm import _conv1d
    inner, taps = h * p, 4
    channels = inner + 2 * heads * n
    if not (conv.supported((batch, seq, channels), taps)
            and gnorm.supported((batch, seq, inner), heads)):
        raise AssertionError("the mixer's stage kernels would not take "
                             "this shape")
    conv_args = (
        jnp.asarray(rng.randn(batch, seq, channels), jnp.bfloat16),
        jnp.asarray(rng.uniform(-0.5, 0.5, (channels, taps)), jnp.float32),
        jnp.asarray(rng.uniform(-0.5, 0.5, channels), jnp.float32),
        jnp.asarray(rng.randn(batch, seq, channels), jnp.float32))
    run(f"causal_conv1d[{batch}x{seq}x{channels},{taps}taps,bf16]",
        lambda *a: (conv.causal_conv1d(*a[:3], activation="silu")
                    .astype(jnp.float32) * a[3]).sum(),
        lambda *a: (_conv1d(*a[:3], activation="silu") * a[3]).sum(),
        conv_args, 3, tol_bf16, 2)
    # the lfm2 family's double-gated short convolution over [b | c | u]
    # (3 x inner channels, 3 taps), bf16 in and out: the file's second pair
    from paddle_tpu.ops.ssm import _gated_conv
    if not conv.gated_supported((batch, seq, 3 * inner), 3):
        raise AssertionError("the gated short convolution's kernels would "
                             "not take this shape")
    gated_args = (
        jnp.asarray(rng.randn(batch, seq, 3 * inner), jnp.bfloat16),
        jnp.asarray(rng.uniform(-0.5, 0.5, (inner, 3)), jnp.float32),
        jnp.asarray(rng.randn(batch, seq, inner), jnp.float32))
    run(f"gated_short_conv[{batch}x{seq}x3x{inner},3taps,bf16]",
        lambda *a: (conv.gated_short_conv(*a[:2]).astype(jnp.float32)
                    * a[2]).sum(),
        lambda *a: (_gated_conv(*a[:2]) * a[2]).sum(),
        gated_args, 2, tol_bf16, 2)
    norm_args = (
        jnp.asarray(rng.randn(batch, seq, inner), jnp.bfloat16),
        jnp.asarray(rng.randn(batch, seq, inner), jnp.bfloat16),
        jnp.asarray(1.0 + 0.1 * rng.randn(inner), jnp.float32),
        jnp.asarray(rng.randn(batch, seq, inner), jnp.float32))
    run(f"gated_rms_norm[{batch}x{seq}x{inner},{heads}groups,bf16]",
        lambda *a: (gnorm.gated_rms_norm(*a[:3], epsilon=1e-5,
                                         num_groups=heads)
                    .astype(jnp.float32) * a[3]).sum(),
        lambda *a: (_rms_norm(*a[:3], epsilon=1e-5, num_groups=heads,
                              gated=True, scaled=True) * a[3]).sum(),
        norm_args, 3, tol_bf16, 2)

    # a projection's result on its way to the flash kernels: (batch, seq,
    # heads x 128) bf16 through the head norm and the rotation at given
    # positions (both halves of the rows at one position, as block
    # diffusion sends them) into (batch, heads, seq, 128)
    from paddle_tpu.ops.nn_ops import _qk_heads, _rotary_frequencies
    from paddle_tpu.ops.pallas import qk_heads as qkh
    if not qkh.supported((batch, seq, heads * 128), heads, (seq,)):
        raise AssertionError("the head kernels would not take this shape")
    head_attrs = dict(heads=heads, epsilon=1e-6, normed=True, positioned=True,
                      freq=tuple(_rotary_frequencies(128, 1e6,
                                                     "smoke").tolist()))
    head_args = (
        jnp.asarray(rng.randn(batch, seq, heads * 128), jnp.bfloat16),
        jnp.asarray(1.0 + 0.1 * rng.randn(128), jnp.float32),
        jnp.asarray(rng.randn(batch, heads, seq, 128), jnp.float32),
        jnp.asarray(np.tile(np.arange(seq // 2), 2), jnp.int32))
    run(f"qk_heads[{batch}x{seq}x{heads}x128,norm+rotary,bf16]",
        lambda *a: (qkh.qk_heads(*a[:2], a[3], **head_attrs)
                    .astype(jnp.float32) * a[2]).sum(),
        lambda *a: (_qk_heads(*a[:2], a[3], **head_attrs) * a[2]).sum(),
        head_args, 2, tol_bf16, 2)

    # a latent attention's three projected arrays (batch, seq, heads x
    # (128 + 64)), (batch, seq, heads x (128 + 128)) and the one rotary
    # key head (batch, seq, 64) bf16 into q, k (batch, heads, seq, 192)
    # and v (batch, heads, seq, 128): interleaved rotation, the key head
    # behind every head, K split from V
    from paddle_tpu.ops.nn_ops import _mla_heads
    from paddle_tpu.ops.pallas import mla_heads as mlh
    widths = (heads * 192, heads * 256, 64)
    if not mlh.supported(*((batch, seq, n) for n in widths), heads, 128, 128,
                         [jnp.bfloat16] * 3):
        raise AssertionError("the latent heads' kernels would not take this "
                             "shape")
    latent_attrs = dict(heads=heads, nope=128, v=128, freq=tuple(
        _rotary_frequencies(64, 1e4, "smoke").tolist()))
    latent_args = tuple(
        jnp.asarray(rng.randn(batch, seq, n), jnp.bfloat16) for n in widths
    ) + tuple(jnp.asarray(rng.randn(batch, heads, seq, d), jnp.float32)
              for d in (192, 192, 128))

    def latent_loss(fn):
        return lambda *a: sum((y.astype(jnp.float32) * ct).sum() for y, ct
                              in zip(fn(*a[:3], **latent_attrs), a[3:]))

    run(f"mla_heads[{batch}x{seq}x{heads}x(128+64|128+128),bf16]",
        latent_loss(mlh.mla_heads), latent_loss(_mla_heads), latent_args, 3,
        tol_bf16, 2)

    # routed experts over (rows, hidden) bf16, 4 held of 16, top-2, gated:
    # the combine through the in-place scatter-add kernel, forward and dx
    from paddle_tpu.ops import moe
    width, held, k = hidden // 4, 4, 2
    if not P.moe_scatter_add_mod.supported(
            hidden, moe._ladder(rows, moe.MIN_ROWS)):
        raise AssertionError("the scatter-add kernel would not take this "
                             "shape")
    moe_args = (
        jnp.asarray(rng.randn(1, rows, hidden), jnp.bfloat16),
        jnp.asarray(rng.uniform(0.1, 1.0, (1, rows, k)), jnp.float32),
        *(jnp.asarray(0.05 * rng.randn(*shape), jnp.float32)
          for shape in ((held, hidden, width), (held, width, hidden),
                        (held, hidden, width))),
        jnp.asarray(rng.randn(1, rows, hidden), jnp.float32),
        jnp.asarray(np.argsort(rng.rand(1, rows, 16))[..., :k], jnp.int32))

    def experts(kernel, dot_dtype):
        def loss(x, weights, up, down, gate, ct, chosen):
            y, _ = moe._routed(x, chosen, weights, up, down, gate, first=0,
                               dot_dtype=dot_dtype, kernel=kernel)
            return (y.astype(jnp.float32) * ct).sum()
        return loss

    run(f"moe_scatter_add[{rows}x{hidden},{held}x{width}gated,bf16]",
        experts(True, jnp.bfloat16), experts(False, jnp.float32), moe_args,
        5, tol_bf16, 2)

    # the same layer through the grouped products (one sort, rows padded
    # to the row tile, five kernels and the scatter-add), experts whole
    # 128-lane tiles wide; off the chip at a tile the interpreter can walk
    wide = -(-width // 128) * 128
    tile, chunk = (moe.ROW_TILE, moe.CHUNK_ROWS) if on_chip else (16, 64)
    if not P.moe_grouped_mod.supported(hidden, wide, tile):
        raise AssertionError("the grouped kernels would not take this shape")
    grouped_args = moe_args[:2] + tuple(
        jnp.asarray(0.05 * rng.randn(*shape), jnp.float32)
        for shape in ((held, hidden, wide), (held, wide, hidden),
                      (held, hidden, wide))) + moe_args[5:]

    def grouped(x, weights, up, down, gate, ct, chosen):
        y, _ = moe._tiles(x[0].astype(jnp.bfloat16), weights[0], (gate, up),
                          down, chosen[0], 0, tile, chunk, jnp.bfloat16,
                          False)
        return (y * ct[0]).sum()

    run(f"moe_grouped[{rows}x{hidden},{held}x{wide}gated,bf16]",
        grouped, experts(False, jnp.float32), grouped_args, 5, tol_bf16, 9)

    # a learned sparse attention over (2, heads, seq, 128) bf16 with an
    # indexer of 4 heads of 64 that keeps a quarter of the keys a row: the
    # selection kernel against its definition (a sort; both leave the
    # selection as packed bits), then attention under that ONE selection,
    # unpacked, and the indexer's loss, the three kernels against the
    # definition routes. The sequence is the least the selection kernel
    # takes: whole chunks of keys in each bit of a packed element
    from paddle_tpu.ops import sparse_attention as sa
    from paddle_tpu.ops.pallas import dsa
    from paddle_tpu.ops.pallas.flash_attention import _flash_sel
    tiles = dict(rows=dsa.SELECT_ROWS, chunk=dsa.SELECT_CHUNK) \
        if on_chip else dict(rows=32, chunk=128)
    seq = max(seq, sa.PACK * tiles["chunk"])
    hi, di, top_k = 4, 64, seq // 4
    if not (dsa.select_supported((2, hi, seq, di), **tiles)
            and dsa.kl_supported((2, heads, seq, 128), (2, hi, seq, di),
                                 min(dsa.KL_BLOCK, seq))):
        raise AssertionError("the indexer's kernels would not take this "
                             "shape")
    index_args = (jnp.asarray(rng.randn(2, hi, seq, di), jnp.bfloat16),
                  jnp.asarray(rng.randn(2, seq, di), jnp.bfloat16),
                  jnp.asarray(rng.randn(2, seq, hi) / 16.0, jnp.float32))

    def select(route, **tiles):
        bits, *rest = route(*index_args, top_k=top_k, **tiles)
        return (sa._unpack(bits, seq), *rest)

    selected, lse, _, pairs = jax.jit(lambda: select(dsa.select, **tiles))()
    want = jax.jit(lambda: select(sa._select))()
    apart = int(jnp.sum(selected != want[0]))
    say("kernels", kernel=f"dsa_select[2x{seq},{hi}x{di},top{top_k}]",
        selected_pairs=int(jnp.sum(pairs)), differ_from_a_sort=apart)
    if apart > 2e-3 * int(jnp.sum(pairs)):
        raise AssertionError(f"dsa_select: {apart} pairs apart from the "
                             f"definition's selection")
    sparse_args = tuple(
        jnp.asarray(rng.randn(2, heads, seq, 128), jnp.bfloat16)
        for _ in range(3)) + index_args + (
        jnp.asarray(rng.randn(2, heads, seq, 128), jnp.float32),
        selected, lse)

    def sparse(kernel):
        def loss(q, k, v, qi, ki, w, ct, selected, lse):
            o, m, l = _flash_sel(q, k, v, selected, None, 512, 512) \
                if kernel else sa.selected_attention(q, k, v, selected)
            return (o.astype(jnp.float32) * ct).sum() \
                + 100.0 * sa._indexer_loss(q, k, m, l, selected, qi, ki, w,
                                           lse, None, kernel)
        return loss

    run(f"sparse_attention[2x{heads}x{seq}x128,{hi}x{di},top{top_k},bf16]",
        sparse(True), sparse(False), sparse_args, 6, tol_bf16, 3)


# ---------------------------------------------------------------------------
# train


def _bert_batch(cfg, batch, seq, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (batch, seq)).astype("i4")
    mlm = np.where(rng.rand(batch, seq) < 0.15,
                   rng.randint(0, cfg.vocab_size, (batch, seq)),
                   -1).astype("i4")
    nsp = rng.randint(0, 2, (batch,)).astype("i4")
    return ids, mlm, nsp


def _bert_trainer(cfg, wrap=None):
    """(model, optimizer, compiled step) — the path of the benchmark's
    BERT cells (benchmark/families/bert_pretrain.py), one optimizer step
    per call. ``wrap(model, opt)`` lets
    the fleet phase place both on its mesh."""
    import paddle_tpu as pt
    from paddle_tpu import amp, jit, optimizer as opt
    from paddle_tpu.models.bert import BertForPretraining

    pt.seed(0)
    model = BertForPretraining(cfg)
    o = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())
    if wrap is not None:
        model, o = wrap(model, o)

    def bert_step(ids, mlm, nsp):
        with amp.auto_cast(dtype="bfloat16"):
            logits, nsp_logits = model(ids)
        loss = model.loss(logits.astype("float32"),
                          nsp_logits.astype("float32"), mlm, nsp)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    return model, o, jit.to_static(bert_step, models=[model],
                                   optimizers=[o])


def _state_arrays(model, o):
    out = [p.data for p in model.parameters()]
    out += [t.data for slots in o._accumulators.values()
            for t in slots.values()]
    return out


def phase_train(cfg, batch, seq, steps, flash_batch, flash_seq,
                flash_steps):
    """The trainer at the model's full width: ``steps`` optimizer steps
    on one fixed seeded batch at (batch, seq), then ``flash_steps`` at
    (flash_batch, flash_seq), a shape whose step holds the flash kernel."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu import monitor
    from paddle_tpu.ops import pallas as P

    on_chip = pt.device.is_tpu_backend()
    monitor.enable()
    monitor.xla.reset()
    reg = monitor.registry()

    def count(name):
        return int(reg.value(name, 0))

    c0, r0 = count("jit.compile"), count("jit.recompile")
    model, o, step = _bert_trainer(cfg)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    say("train", model="BertForPretraining", layers=cfg.num_hidden_layers,
        hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
        vocab=cfg.vocab_size, params=n_params)
    dev = jax.devices()[0]

    def run_shape(b, s, n, kernels, must_fall):
        feed = [pt.to_tensor(a) for a in _bert_batch(cfg, b, s)]
        seen = count("jit.compile")
        t0 = time.perf_counter()
        losses = [float(step(*feed).numpy())]
        say("train", shape=f"{b}x{s}", first_call_s=
            f"{time.perf_counter() - t0:.1f}", note="trace+compile+1 step")
        if count("jit.compile") != seen + 1:
            raise AssertionError("first call at a new shape must compile "
                                 "exactly once")
        exe = monitor.xla.executable()
        if exe is None:
            raise AssertionError("the compiled step was not captured")
        calls = _custom_calls(exe)
        say("train", shape=f"{b}x{s}", tpu_custom_calls=calls,
            kernels_expected=",".join(kernels))
        # the defaults promise these kernels inside the step: two norms
        # a layer + the embeddings' + the MLM head's, each a forward and
        # a backward kernel; flash is a forward and a backward kernel per
        # layer (one pass for dq, dk and dv since PR 40)
        layers = cfg.num_hidden_layers
        want = 2 * (2 * layers + 2) * ("layer_norm" in kernels) \
            + 2 * layers * ("flash_attention" in kernels)
        if on_chip and calls < want:
            raise AssertionError(
                f"step {b}x{s} holds {calls} tpu_custom_call(s), the "
                f"defaults promise at least {want} ({kernels})")
        t0 = time.perf_counter()
        rest = [step(*feed) for _ in range(n - 1)]
        jax.block_until_ready(rest[-1].data)
        dt = (time.perf_counter() - t0) / (n - 1)
        losses += [float(t.numpy()) for t in rest]
        say("train", shape=f"{b}x{s}", losses=" ".join(
            f"{x:.4f}" for x in losses))
        if not np.isfinite(losses).all():
            raise AssertionError(f"non-finite loss at {b}x{s}")
        if must_fall and not losses[-1] < losses[0]:
            raise AssertionError(f"loss did not fall: {losses}")
        say("train", shape=f"{b}x{s}",
            step_time_ms=f"{dt * 1e3:.2f}", tokens_per_s=f"{b * s / dt:.0f}",
            note=f"host clock around block_until_ready, warm, "
                 f"{n - 1} steps, single builder run")
        if count("jit.compile") != seen + 1:
            raise AssertionError("a warm step compiled again")

    run_shape(batch, seq, steps, ["layer_norm"], must_fall=True)
    if (count("jit.compile") - c0, count("jit.recompile") - r0) != (1, 0):
        raise AssertionError("the first shape must be exactly one compile")
    flash_on = P.enabled("flash_attention", seq_len=flash_seq)
    if on_chip and not flash_on:
        raise AssertionError(f"flash is off at seq {flash_seq}")
    run_shape(flash_batch, flash_seq, flash_steps,
              ["layer_norm", "flash_attention"] if flash_on
              else ["layer_norm"], must_fall=False)
    compiles = count("jit.compile") - c0
    # a second SHAPE of one function is what the counter calls a
    # recompile; per shape there is exactly one compile (checked above)
    recompiles = count("jit.recompile") - r0
    say("train", jit_compile=compiles, jit_recompile=recompiles,
        note="one compile per shape; the seq-%d shape is the one "
             "recompile" % flash_seq)
    if compiles != 2 or recompiles != 1:
        raise AssertionError("expected one compile per shape")
    # donated state was replaced, never read back: every live payload is
    # a valid array on the accelerator
    state = _state_arrays(model, o)
    for a in state:
        if a.is_deleted():
            raise AssertionError("a parameter or slot points at a donated "
                                 "(deleted) buffer")
        if a.devices() != {dev}:
            raise AssertionError(f"state on {a.devices()}, not {dev}")
    say("train", state_arrays=len(state), all_on=str(dev))
    monitor.disable()


# ---------------------------------------------------------------------------
# serve


def phase_serve(dim, heads, layers, n_requests, slots, page, max_len,
                prompt_buckets, drain_new_tokens):
    """The decode server: ragged requests through continuous batching
    equal the same requests one at a time; no executable after warm-up;
    a replica drained mid-stream hands its streams over unchanged."""
    import jax
    from paddle_tpu import serving
    from paddle_tpu.serving.disagg import DisaggServer

    model = serving.demo_model(dim=dim, heads=heads, layers=layers,
                               max_len=max_len, seed=1)
    say("serve", model="DemoLM (the only model the server has)", dim=dim,
        heads=heads, layers=layers, vocab=model.vocab, dtype="float32")
    rng = np.random.RandomState(0)
    jobs = []
    for _ in range(n_requests):
        p = int(rng.randint(2, prompt_buckets[-1] + 1))
        n = int(rng.randint(4, max(5, min(48, max_len - p))))
        jobs.append((rng.randint(0, model.vocab, (p,)).tolist(), n))
    kw = dict(slots=slots, page=page, factor=2.0, max_len=max_len,
              prompt_buckets=prompt_buckets)

    eng = serving.GenerateEngine(model, **kw)
    t0 = time.perf_counter()
    minted = eng.warmup()
    warm = eng.executables()
    say("serve", warmup_executables=minted,
        warmup_s=f"{time.perf_counter() - t0:.1f}")
    t0 = time.perf_counter()
    futs = [eng.submit(p, max_new_tokens=n) for p, n in jobs]
    batched = [[int(t) for t in f.result(timeout=300)] for f in futs]
    dt = time.perf_counter() - t0
    st = eng.stats()
    say("serve", requests=len(jobs), tokens=sum(map(len, batched)),
        ticks=st["ticks"], avg_occupancy=f"{st['avg_occupancy']:.2f}",
        grows=st["grows"], wall_s=f"{dt:.2f}",
        note="host clock, continuous batching, single builder run")
    single = [[int(t) for t in eng.run(p, max_new_tokens=n, timeout=300)]
              for p, n in jobs]
    if batched != single:
        bad = [i for i, (a, b) in enumerate(zip(batched, single)) if a != b]
        raise AssertionError(f"continuous batching != one at a time for "
                             f"requests {bad}")
    if eng.executables() != warm:
        raise AssertionError(f"executables after warm-up: {warm} -> "
                             f"{eng.executables()}")
    say("serve", batched_equals_single=True,
        executables_after_warmup=eng.executables()[0] - warm[0],
        traces_after_warmup=eng.executables()[1] - warm[1])

    # drain: two decode replicas on this one device, streams in flight,
    # the replica that seated them is drained (KV exported and landed on
    # the peer) — the streams must equal the undrained ones
    long_jobs = [(p, drain_new_tokens) for p, _ in jobs[:slots // 2]]
    want = [[int(t) for t in eng.run(p, max_new_tokens=n, timeout=300)]
            for p, n in long_jobs]
    eng.close()
    dev = jax.local_devices()[0]
    srv = DisaggServer(model, prefill_replicas=1, decode_replicas=2,
                       prefill_devices=[dev], decode_devices=[dev, dev],
                       supervise=False, **kw)
    t0 = time.perf_counter()
    srv.warmup()
    say("serve", disagg_replicas="1 prefill + 2 decode", device=str(dev),
        warmup_s=f"{time.perf_counter() - t0:.1f}")
    futs = [srv.submit(p, max_new_tokens=n) for p, n in long_jobs]
    deadline = time.monotonic() + 120
    victim = None
    while victim is None:
        if time.monotonic() > deadline:
            raise AssertionError("no decode replica seated a stream")
        for r in srv.decode_pool._replicas:
            s = r.engine.stats()
            if s["active_slots"] > 0 and s["ticks"] > 0:
                victim = r
                break
        time.sleep(0.002)
    moved = srv.drain_decode_replica(victim.index, reason="chip_smoke")
    got = [[int(t) for t in f.result(timeout=300)] for f in futs]
    imports = [r.engine.stats()["kv_imports"]
               for r in srv.decode_pool._replicas]
    srv.close()
    say("serve", drained_replica=victim.index, streams_moved=moved,
        kv_imports_per_replica=imports)
    if moved < 1:
        raise AssertionError("the drain found no stream in flight")
    if got != want:
        raise AssertionError("drained streams differ from the undrained "
                             "run")
    say("serve", drained_equals_undrained=True)


# ---------------------------------------------------------------------------
# fleet (four chips)


def phase_fleet(cfg, batch, seq, steps, mesh_shape, tol):
    """The user-facing SPMD path — Fleet + DistributedStrategy.mesh_shape
    — against the same model, seed and global batch on one device."""
    import jax
    import paddle_tpu as pt
    from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet

    devs = jax.devices()
    n_dev = int(np.prod(list(mesh_shape.values())))
    if len(devs) < n_dev:
        raise AssertionError(f"mesh {mesh_shape} needs {n_dev} devices")
    batch_np = _bert_batch(cfg, batch, seq)

    # the comparison: everything on device 0
    with jax.default_device(devs[0]):
        _, _, step1 = _bert_trainer(cfg)
        feed = [pt.to_tensor(a) for a in batch_np]
        ref = [float(step1(*feed).numpy()) for _ in range(steps)]
    say("fleet", one_device_losses=" ".join(f"{x:.4f}" for x in ref))

    fleet = Fleet()
    st = DistributedStrategy()
    st.mesh_shape = dict(mesh_shape)
    fleet.init(strategy=st, devices=devs[:n_dev])

    def wrap(model, o):
        return fleet.distributed_model(model), fleet.distributed_optimizer(o)

    model, o, step = _bert_trainer(cfg, wrap=wrap)
    feed = fleet.shard_batch(*[pt.to_tensor(a) for a in batch_np])
    got = [float(step(*feed).numpy()) for _ in range(steps)]
    say("fleet", mesh=mesh_shape, losses=" ".join(f"{x:.4f}" for x in got))
    worst = max(abs(a - b) / max(1.0, abs(b)) for a, b in zip(got, ref))
    say("fleet", max_rel_loss_diff=f"{worst:.2e}", tol=tol)
    if not np.isfinite(got).all() or worst > tol:
        raise AssertionError(f"fleet losses {got} vs one device {ref}")

    # where the bytes are: per parameter group, per device
    mesh_devs = list(fleet.mesh.devices.flat)
    groups = {}
    sharded_whole_on_0 = []
    for name, p in model.named_parameters():
        a = p.data
        per = {d: 0 for d in mesh_devs}
        for sh in a.addressable_shards:
            per[sh.device] += sh.data.nbytes
        spec = getattr(a.sharding, "spec", ())
        is_tp = any(ax is not None for ax in spec)
        if is_tp and per[mesh_devs[0]] >= a.nbytes:
            sharded_whole_on_0.append(name)
        g = groups.setdefault(
            ("tp-sharded " if is_tp else "replicated ")
            + name.split(".")[-2 if "." in name else 0]
            + "." + name.split(".")[-1], {d: 0 for d in mesh_devs})
        for d, n in per.items():
            g[d] += n
    total = {d: 0 for d in mesh_devs}
    for gname in sorted(groups):
        per = groups[gname]
        say("fleet", group=repr(gname),
            bytes_per_device=[per[d] for d in mesh_devs])
        for d in mesh_devs:
            total[d] += per[d]
    say("fleet", total_param_bytes_per_device=[total[d] for d in mesh_devs])
    if sharded_whole_on_0:
        raise AssertionError(f"tp-sharded weights whole on device 0: "
                             f"{sharded_whole_on_0[:5]}")
    if not any(k.startswith("tp-sharded") for k in groups):
        raise AssertionError("no weight is tp-sharded")
    if sum(1 for d in mesh_devs if total[d] == 0) >= n_dev - 1:
        raise AssertionError("all parameter bytes sit on one device")


# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips
    device = phase_device(chips)
    from paddle_tpu.models.bert import BertConfig
    cfg = BertConfig.base()     # 12 layers, hidden 768, 12 heads, 30,522
    if chips == 4:
        phase_fleet(cfg, batch=64, seq=128, steps=4,
                    mesh_shape={"dp": 2, "tp": 2}, tol=2e-2)
    else:
        phase_kernels(rows=64 * 128, hidden=768, batch=16, heads=12,
                      seq=512, head_dim=64)
        phase_train(cfg, batch=64, seq=128, steps=10, flash_batch=16,
                    flash_seq=512, flash_steps=3)
        phase_serve(dim=256, heads=4, layers=2, n_requests=16, slots=8,
                    page=32, max_len=128, prompt_buckets=(16, 32, 64),
                    drain_new_tokens=64)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
