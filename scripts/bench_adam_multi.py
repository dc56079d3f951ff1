"""Adam update ablation on the real chip: XLA's own fusion vs the
per-tensor Pallas kernel vs the r5 multi-tensor (one-dispatch) kernel,
over the real BERT-base parameter set (~110M params, 200+ tensors).

Methodology: each variant jits a fori-free python
chain of `iters` sequential updates with state threading, so the host
dispatch cost amortizes and the device actually executes every update
(outputs feed inputs; nothing is dead-code eliminated).

The decision rule for _AUTO_ON['fused_adam_multi'] is printed at the
end: multi wins only if it beats the XLA baseline.

Run: python -u scripts/bench_adam_multi.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def param_set():
    """The real BERT-base pretraining parameter shapes."""
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    import paddle_tpu as pt
    pt.seed(0)
    model = BertForPretraining(BertConfig.base())
    shapes = [tuple(p.data.shape) for p in model.parameters()
              if not p.stop_gradient]
    del model
    return shapes


def bench(mode, shapes, iters=10):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.fused_adam import (
        adam_step, fused_adam_update_multi)

    rng = np.random.RandomState(0)
    ps = [jnp.asarray(rng.randn(*s).astype("f4") * 0.02) for s in shapes]
    gs = [jnp.asarray(rng.randn(*s).astype("f4") * 1e-3) for s in shapes]
    ms = [jnp.zeros(s, jnp.float32) for s in shapes]
    vs = [jnp.zeros(s, jnp.float32) for s in shapes]

    def one(ps, ms, vs, b1p, b2p):
        if mode == "multi":
            nps, nms, nvs = fused_adam_update_multi(
                ps, gs, ms, vs, 1e-4, b1p, b2p)
        else:
            nps, nms, nvs = [], [], []
            for p, g, m, v in zip(ps, gs, ms, vs):
                np_, nm, nv = adam_step(p, g, m, v, 1e-4, b1p, b2p,
                                        use_fused=(mode == "pallas"))
                nps.append(np_)
                nms.append(nm)
                nvs.append(nv)
        return nps, nms, nvs

    @jax.jit
    def chain(ps, ms, vs):
        b1p, b2p = jnp.float32(1.0), jnp.float32(1.0)
        for _ in range(iters):
            b1p, b2p = b1p * 0.9, b2p * 0.999
            ps, ms, vs = one(ps, ms, vs, b1p, b2p)
        return ps, ms, vs

    out = chain(ps, ms, vs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = chain(ps, ms, vs)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    n = sum(int(np.prod(s)) for s in shapes)
    # ideal traffic: read p,g,m,v + write p,m,v = 7 x 4B x n
    gbs = 7 * 4 * n / dt / 1e9
    return dt * 1e3, gbs


def main():
    from paddle_tpu.device import enable_compilation_cache
    enable_compilation_cache()
    shapes = param_set()
    n = sum(int(np.prod(s)) for s in shapes)
    print(f"param set: {len(shapes)} tensors, {n / 1e6:.1f}M params",
          flush=True)
    results = {}
    for mode in ("xla", "pallas", "multi"):
        try:
            ms, gbs = bench(mode, shapes)
            results[mode] = ms
            print(f"adam {mode:>6}: {ms:8.3f} ms/step  "
                  f"({gbs:6.0f} GB/s update-traffic equiv)", flush=True)
        except Exception as e:
            print(f"adam {mode:>6}: FAIL {type(e).__name__}: {e}",
                  flush=True)
    if "xla" in results and "multi" in results:
        win = results["multi"] < results["xla"]
        rel = (results["xla"] - results["multi"]) / results["xla"] * 100
        print(f"multi vs xla: {rel:+.1f}%  -> "
              f"{'FLIP fused_adam_multi AUTO-ON' if win else 'keep auto-off'}",
              flush=True)


if __name__ == "__main__":
    main()
