"""BERT train-step ablation on the real chip: flash / pallas-LN /
fused-adam each on-off, batch 32 and 64. Prints tok/s for each combo."""
import itertools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def bench(batch, seq, flash, pallas_ln, fused_adam, xent, steps=16,
          inner=4, adam_multi=False):
    """`inner` real optimizer steps per compiled call (same amortization
    as bench.py): per-dispatch host overhead would
    otherwise drown the per-kernel deltas this ablation exists to
    measure."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt, jit, amp
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu.ops import pallas as P

    pt.seed(0)
    # flash_min_seq=0: the ablation exists to measure BOTH sides of the
    # crossover, so the seq gate must not silently reroute flash=1 rows
    # to sdpa at seq 128
    P.configure(flash_attention=flash, layer_norm=pallas_ln,
                fused_adam=fused_adam, softmax_xent=xent, flash_min_seq=0,
                fused_adam_multi=adam_multi)
    cfg = BertConfig.base(use_flash_attention=flash)
    model = BertForPretraining(cfg)
    o = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (inner, batch, seq)).astype("i4")
    mlm = np.where(rng.rand(inner, batch, seq) < 0.15,
                   rng.randint(0, cfg.vocab_size, (inner, batch, seq)),
                   -1).astype("i4")
    nsp = rng.randint(0, 2, (inner, batch)).astype("i4")

    def one(ids, mlm, nsp):
        with amp.auto_cast(dtype="bfloat16"):
            logits, nsp_logits = model(ids)
        loss = model.loss(logits.astype("float32"),
                          nsp_logits.astype("float32"), mlm, nsp)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    def step(ids_k, mlm_k, nsp_k):
        loss = None
        for i in range(inner):
            loss = one(ids_k[i], mlm_k[i], nsp_k[i])
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o])
    t_ids, t_mlm, t_nsp = pt.to_tensor(ids), pt.to_tensor(mlm), \
        pt.to_tensor(nsp)
    fn(t_ids, t_mlm, t_nsp)
    loss = fn(t_ids, t_mlm, t_nsp)
    loss.numpy()
    n_calls = max(1, steps // inner)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        loss = fn(t_ids, t_mlm, t_nsp)
    loss.numpy()
    dt = (time.perf_counter() - t0) / (n_calls * inner)
    return batch * seq / dt, float(loss.numpy())


CONFIGS = [
    # (batch, flash, pallas_ln, fused_adam, softmax_xent)
    (32, 0, 0, 0, 0),
    (32, 1, 0, 0, 0),
    (32, 0, 1, 0, 0),
    (32, 0, 0, 1, 0),
    (32, 0, 0, 0, 1),
    (32, 1, 1, 1, 1),
    (64, 0, 0, 0, 0),
    (64, 1, 1, 1, 1),
]


def main():
    for batch, flash, ln, fa, xe in CONFIGS:
        try:
            tps, loss = bench(batch, 128, bool(flash), bool(ln),
                              bool(fa), bool(xe))
            print(f"batch={batch} flash={flash} ln={ln} "
                  f"adam={fa} xent={xe}: {tps:,.0f} tok/s "
                  f"loss={loss:.4f}", flush=True)
        except Exception as e:
            print(f"batch={batch} flash={flash} ln={ln} "
                  f"adam={fa} xent={xe}: FAIL {type(e).__name__}: {e}",
                  flush=True)
    # full-model multi-tensor adam row (r5): one dispatch over all params
    # vs XLA's fused update, in situ at the headline shape
    for multi in (0, 1):
        try:
            tps, _ = bench(64, 128, True, True, False, False,
                           adam_multi=bool(multi))
            print(f"batch=64 adam_multi={multi}: {tps:,.0f} tok/s",
                  flush=True)
        except Exception as e:
            print(f"batch=64 adam_multi={multi}: FAIL "
                  f"{type(e).__name__}: {e}", flush=True)
    # full-model check of the flash_min_seq=512 crossover (the sweep's
    # kernel-only verdict at 512 was a wash; this decides it in situ)
    for flash in (0, 1):
        try:
            tps, _ = bench(16, 512, bool(flash), True, False, False,
                           steps=8, inner=2)
            print(f"seq=512 batch=16 flash={flash}: {tps:,.0f} tok/s",
                  flush=True)
        except Exception as e:
            print(f"seq=512 batch=16 flash={flash}: FAIL "
                  f"{type(e).__name__}: {e}", flush=True)


if __name__ == "__main__":
    main()
