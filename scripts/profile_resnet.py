"""ResNet-50 perf triage on the real chip: where does the step time go?

Measurement rules: repeated
identical dispatches are served from cache and `block_until_ready` is
not a real sync, so (a) the conv/matmul ceilings use a fori_loop
dependency CHAIN with a scalar D2H at the end, and (b) the model rows
time full train steps (optimizer state advances every call) with a
final `.numpy()`. The per-call fixed overhead (~66 ms) is reported
separately via a 16-vs-64-iteration chain solve.

Also writes a jax.profiler trace of the train step and prints the
per-op-family table via utils.profiler.summarize_trace — the view that
found BN's reduce chains at ~70% of the r4 step.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp


def chained(make_body, x0, iters):
    """Time `iters` chained applications with one scalar D2H sync."""
    @jax.jit
    def chain(x):
        def body(i, x):
            return make_body(x)
        out = jax.lax.fori_loop(0, iters, body, x)
        return jnp.ravel(out)[0]

    float(chain(x0))  # compile + warm
    t0 = time.perf_counter()
    float(chain(x0))
    return time.perf_counter() - t0


def conv_ceiling(batch, layout="NHWC"):
    """Marginal time of the biggest-FLOP resnet conv (layer3 3x3) from
    a 16-vs-64 chain solve; returns (marginal_ms, TF/s, fixed_ms)."""
    rng = np.random.RandomState(0)
    if layout == "NHWC":
        x = jnp.asarray(rng.randn(batch, 28, 28, 256) * 0.1, jnp.bfloat16)
        w = jnp.asarray(rng.randn(3, 3, 256, 256) * 0.01, jnp.bfloat16)
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        x = jnp.asarray(rng.randn(batch, 256, 28, 28) * 0.1, jnp.bfloat16)
        w = jnp.asarray(rng.randn(256, 256, 3, 3) * 0.01, jnp.bfloat16)
        dn = ("NCHW", "OIHW", "NCHW")

    def body(x):
        return jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME", dimension_numbers=dn) * 0.01

    # min-of-3 per point: the t64−t16 difference being solved for
    # (~29 ms) is smaller than one bad HTTP-dispatch jitter spike
    t16 = min(chained(body, x, 16) for _ in range(3))
    t64 = min(chained(body, x, 64) for _ in range(3))
    marginal = (t64 - t16) / 48
    fixed = t16 - 16 * marginal
    flops = 2 * batch * 28 * 28 * 256 * 256 * 9
    return marginal * 1e3, flops / marginal / 1e12, fixed * 1e3


def train_step_rate(batch, data_format="NCHW", inner=8, trace_dir=None):
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt, jit, amp
    from paddle_tpu.models.resnet import resnet50

    pt.seed(0)
    model = resnet50(data_format=data_format)
    o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                     parameters=model.parameters())
    rng = np.random.RandomState(0)
    shape = (inner, batch, 3, 224, 224) if data_format == "NCHW" else \
        (inner, batch, 224, 224, 3)
    x = rng.rand(*shape).astype("f4")
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
    if trace_dir:
        with jax.profiler.trace(trace_dir):
            fn(tx, ty).numpy()
    return batch / dt, dt * 1e3


def main():
    from paddle_tpu.device import enable_compilation_cache
    enable_compilation_cache()
    from paddle_tpu import monitor
    monitor.enable()          # in-memory counters + xla capture
    monitor.profile.enable()  # named scopes -> attributable step HLO
    for layout in ("NHWC", "NCHW"):
        ms, tf, fixed = conv_ceiling(128, layout)
        print(f"conv3x3 b128 {layout}: marginal {ms:.3f} ms "
              f"({tf:.0f} TF/s), fixed/dispatch {fixed:.0f} ms",
              flush=True)
    trace_dir = "/tmp/paddle_tpu_profile_resnet"
    for batch, df, td in ((128, "NCHW", trace_dir), (128, "NHWC", None),
                          (256, "NCHW", None)):
        ips, ms = train_step_rate(batch, df, trace_dir=td)
        print(f"train b{batch} {df}: {ms:.1f} ms/step ({ips:,.0f} img/s)",
              flush=True)
    from paddle_tpu.utils.profiler import summarize_trace
    summarize_trace(trace_dir, steps=8)  # the traced call runs inner=8
    # the attributed cost ledger of the newest captured train step:
    # which region tops the fusion menu, at what attributed fraction —
    # the trace view above says WHAT is slow, this says WHOSE it is
    rep = monitor.profile.report(top_k=12, emit_records=False)
    if rep is not None:
        print(flush=True)
        print(monitor.profile.format_table(rep, top_k=12), flush=True)


if __name__ == "__main__":
    main()
