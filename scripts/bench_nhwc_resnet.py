"""End-to-end ResNet-50 train-step ablation on the real chip:
NCHW (the r4 headline layout) vs NHWC vs NHWC + fused Pallas BN.

This is the measurement VERDICT r4 task 2 asks for: the r4 roofline
(docs/performance.md, superseded-toolchain records) showed BN's memory-bound chains at ~70% of the NCHW
step and named "fused stats+normalize Pallas BN, NHWC-native layout" as
the fix — this script decides whether to flip the headline layout and
_AUTO_ON['batch_norm'].

Methodology: `inner` real optimizer steps chained in
one compiled call over distinct resident uint8 batches (normalize on
device), so host dispatch amortizes.

Run: python -u scripts/bench_nhwc_resnet.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def run(data_format, pallas_bn, batch=128, inner=4, calls=3):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt, jit, amp
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.ops import pallas as P

    P.configure(batch_norm=pallas_bn)
    try:
        pt.seed(0)
        model = resnet50(data_format=data_format)
        o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                         parameters=model.parameters())
        rng = np.random.RandomState(0)
        shape = (inner, batch, 3, 224, 224) if data_format == "NCHW" \
            else (inner, batch, 224, 224, 3)
        x = (rng.rand(*shape) * 255).astype("u1")
        y = rng.randint(0, 1000, (inner, batch)).astype("i4")

        def norm(xb):
            return (xb.astype("float32") / 255.0 - 0.45) / 0.22

        def one(xb, yb):
            with amp.auto_cast(dtype="bfloat16"):
                logits = model(norm(xb))
            loss = pt.nn.functional.cross_entropy(
                logits.astype("float32"), yb)
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
        loss = None
        for _ in range(calls):
            loss = fn(tx, ty)
        loss.numpy()
        dt = (time.perf_counter() - t0) / (calls * inner)
        return batch / dt, float(loss.numpy())
    finally:
        P.configure(batch_norm=None)


def main():
    from paddle_tpu.device import enable_compilation_cache
    enable_compilation_cache()
    rows = [("NCHW xla-bn", "NCHW", False),
            ("NHWC xla-bn", "NHWC", False),
            ("NHWC pallas-bn", "NHWC", True)]
    results = {}
    for label, fmt, pbn in rows:
        try:
            ips, loss = run(fmt, pbn)
            results[label] = ips
            print(f"resnet50 {label:>15}: {ips:8,.1f} img/s  "
                  f"loss={loss:.4f}", flush=True)
        except Exception as e:
            print(f"resnet50 {label:>15}: FAIL {type(e).__name__}: {e}",
                  flush=True)
    if results:
        best = max(results, key=results.get)
        base = results.get("NCHW xla-bn")
        print(f"winner: {best}" + (
            f"  ({(results[best] / base - 1) * 100:+.1f}% vs NCHW)"
            if base else ""), flush=True)
        if best == "NHWC pallas-bn":
            print("-> flip _AUTO_ON['batch_norm']=True (channels-last) "
                  "and make NHWC the resnet cell's layout", flush=True)
        elif best == "NHWC xla-bn":
            print("-> NHWC for the resnet cell; keep pallas BN off",
                  flush=True)
        else:
            print("-> keep NCHW headline; record table in "
                  "PERF.md", flush=True)


if __name__ == "__main__":
    main()
