"""int8 vs bf16 Predictor throughput on the real chip (VERDICT r3 #7's
bench line). Run: python -u scripts/bench_int8.py

Measures an MXU-bound Linear tower through Predictor.run_device with a
DATA-DEPENDENT CHAIN (each call consumes the previous call's device
output) and a single device→host sync at the end, so that per-call
D2H does not surround sub-ms compute. The tower's output shape equals its input shape so
the chain type-checks; int8 activation scales are calibrated on the
true input distribution but the chain's drifting activations only
affect numerics, not throughput.
"""
import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    from paddle_tpu.device import enable_compilation_cache
    enable_compilation_cache()
    import paddle_tpu as pt
    from paddle_tpu import nn
    from paddle_tpu.inference import Config, Predictor

    pt.seed(0)
    d, layers, batch, steps = 4096, 16, 512, 40
    blocks = []
    for _ in range(layers):
        blocks += [nn.Linear(d, d), nn.ReLU()]
    model = nn.Sequential(*blocks)
    rng = np.random.RandomState(0)
    x = (rng.randn(batch, d) * 0.05).astype("f4")
    cal = [pt.to_tensor(x)]
    gflop_call = 2 * layers * batch * d * d / 1e9

    def rate(predictor):
        y = predictor.run_device(x)       # compile + stage on device
        np.asarray(y[:1, :1])             # sync the warmup
        y = predictor.run_device(x)
        np.asarray(y[:1, :1])             # sync: keep warmup out of t0
        t0 = time.perf_counter()
        for _ in range(steps):
            y = predictor.run_device(y)   # data-dependent chain
        np.asarray(y[:1, :1])             # one tiny D2H sync
        dt = (time.perf_counter() - t0) / steps
        return batch / dt, gflop_call / dt / 1e3  # samples/s, TF/s

    bf16, bf16_tf = rate(Predictor(model, Config().enable_bf16()))
    # enable_int8 quantizes a COPY, so the same model object serves both
    int8, int8_tf = rate(Predictor(model, Config().enable_int8(cal)))
    print(json.dumps({
        "metric": "int8_vs_bf16_inference",
        "bf16_samples_per_sec": round(bf16, 1),
        "int8_samples_per_sec": round(int8, 1),
        "bf16_tf_s": round(bf16_tf, 1),
        "int8_tf_s": round(int8_tf, 1),
        "speedup": round(int8 / bf16, 3),
        "model": f"{layers}x Linear({d},{d}) batch {batch}",
    }))


if __name__ == "__main__":
    main()
