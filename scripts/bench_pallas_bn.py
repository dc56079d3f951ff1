"""Fused Pallas batch-norm vs XLA on the real chip.

Two measurements, both with per-call state advancement (so that no
call repeats an identical dispatch):

1. BN-microbench: chained fwd+bwd over a ResNet-stage-shaped (M, C)
   activation, Pallas kernel vs the one-pass XLA path.
2. Full NHWC ResNet-50 train step (the kernel requires channels-last),
   batch_norm kernel on vs off.

If the kernel wins, flip _AUTO_ON['batch_norm'] (channels-last only).
Run: python -u scripts/bench_pallas_bn.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def micro(use_pallas, m=128 * 28 * 28, c=256, iters=12):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.batch_norm import _batch_norm2

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(m, c), jnp.bfloat16)
    w = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    b = jnp.asarray(rng.randn(c), jnp.float32)

    def xla_bn(x2, w, b, eps=1e-5):
        # Baseline = the PRODUCTION XLA path (shifted one-pass moments +
        # folded scale/shift from nn_ops), not a hand-rolled variant:
        # the auto-on decision must compare the kernel against the exact
        # program it would replace (r4 advisor finding).
        from paddle_tpu.ops.nn_ops import _fold_scale_shift, \
            _one_pass_moments
        mean, var = _one_pass_moments(x2, (0,))
        return _fold_scale_shift(x2, mean, var, w, b, eps, (1, x2.shape[1]))

    bn = (lambda x: _batch_norm2(x, w, b, 1e-5)[0]) if use_pallas \
        else (lambda x: xla_bn(x, w, b))

    @jax.jit
    def chain(x):
        def body(i, x):
            def f(x):
                return jnp.sum(bn(x).astype(jnp.float32)) * 1e-6
            g = jax.grad(f)(x)
            return (x + g.astype(x.dtype)).astype(x.dtype)
        return jax.lax.fori_loop(0, iters, body, x)[0, 0]

    float(chain(x))  # compile + warm
    t0 = time.perf_counter()
    float(chain(x))
    dt = (time.perf_counter() - t0) / iters
    # fwd: 2 reads + 1 write; bwd: 2+2 reads + 1 write (bf16)
    gb = m * c * 2 * 8 / 1e9
    return dt * 1e3, gb / dt


def full_resnet(use_pallas, batch=128, inner=8):
    from paddle_tpu.ops import pallas as P

    P.configure(batch_norm=use_pallas)
    try:
        return _full_resnet_body(batch, inner)
    finally:
        P.configure(batch_norm=None)


def _full_resnet_body(batch, inner):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt, jit, amp
    from paddle_tpu.models.resnet import resnet50

    pt.seed(0)
    model = resnet50(data_format="NHWC")
    o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                     parameters=model.parameters())
    rng = np.random.RandomState(0)
    x = rng.rand(inner, batch, 224, 224, 3).astype("f4")
    y = rng.randint(0, 1000, (inner, batch)).astype("i4")

    def one(xb, yb):
        with amp.auto_cast(dtype="bfloat16"):
            logits = model(xb)
        loss = pt.nn.functional.cross_entropy(logits.astype("float32"), yb)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    def step(x_k, y_k):
        loss = None
        for i in range(inner):
            loss = one(x_k[i], y_k[i])
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o])
    tx, ty = pt.to_tensor(x), pt.to_tensor(y)
    fn(tx, ty)
    fn(tx, ty).numpy()
    t0 = time.perf_counter()
    for _ in range(2):
        loss = fn(tx, ty)
    loss.numpy()
    dt = (time.perf_counter() - t0) / (2 * inner)
    return batch / dt, float(loss.numpy())


def main():
    from paddle_tpu.device import enable_compilation_cache
    enable_compilation_cache()
    for use in (False, True):
        ms, gbs = micro(use)
        print(f"micro  pallas={int(use)}: {ms:7.3f} ms/iter  "
              f"{gbs:6.0f} GB/s effective", flush=True)
    for use in (False, True):
        try:
            ips, loss = full_resnet(use)
            print(f"resnet NHWC pallas={int(use)}: {ips:,.1f} img/s "
                  f"loss={loss:.4f}", flush=True)
        except Exception as e:
            print(f"resnet NHWC pallas={int(use)}: FAIL "
                  f"{type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
