"""Long-context training: sequence parallelism + flash attention +
recompute working together.

    python examples/long_context.py     # 8 local devices (sp=4 x dp=2)

Three pieces compose here (SURVEY §2 row 30):

1. **Ring attention** shards the SEQUENCE over the `sp` mesh axis:
   each device holds S/sp of the tokens, K/V blocks rotate around the
   ICI ring via `ppermute` while a flash-style online softmax
   accumulates — full S×S attention is never materialized, so max
   context length scales linearly with the number of devices.
2. **Flash attention kernel** handles the per-device blocks on TPU
   (seq-gated: engages above the crossover in ops/pallas/__init__.py).
3. **Recompute** (`jax.checkpoint` under the hood) trades FLOPs for the
   activation memory the long sequence would otherwise pin.

On the CPU demo mesh the numbers are tiny; on a TPU pod slice the same
code runs with real shapes — only mesh_shape and the config change.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    import paddle_tpu as pt
    from paddle_tpu.parallel.ring_attention import ring_attention

    # takes the devices there are: a dp2 x sp4 mesh needs eight
    if jax.device_count() < 8:
        raise SystemExit(
            f"long_context.py needs 8 devices (dp2 x sp4); JAX found "
            f"{jax.device_count()} ({jax.default_backend()}). On a CPU "
            f"host: JAX_PLATFORMS=cpu "
            f"XLA_FLAGS=--xla_force_host_platform_device_count=8")
    devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("dp", "sp"))
    B, H, S, D = 4, 8, 1024, 64          # seq 1024 split 4-ways over sp
    rng = np.random.RandomState(0)
    q = rng.randn(B, H, S, D).astype("f4")
    k = rng.randn(B, H, S, D).astype("f4")
    v = rng.randn(B, H, S, D).astype("f4")

    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                       causal=True).data,
        mesh=mesh,
        in_specs=(P("dp", None, "sp", None),) * 3,
        out_specs=P("dp", None, "sp", None), check_vma=False))
    out = np.asarray(ring(q, k, v))
    print(f"ring attention: seq {S} sharded sp=4, out {out.shape}, "
          f"finite={np.isfinite(out).all()}")

    # parity vs single-device causal attention on a slice
    logits = np.einsum("hqd,hkd->hqk", q[0], k[0]) / np.sqrt(D)
    mask = np.tril(np.ones((S, S), bool))
    e = np.exp(np.where(mask, logits, -1e30) -
               np.where(mask, logits, -1e30).max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = np.einsum("hqk,hkd->hqd", p, v[0])
    err = np.abs(out[0] - ref).max()
    print(f"parity vs full causal attention: max|err|={err:.2e}")

    # the same composition through the user-level model: long-seq BERT
    # with recompute (flash engages automatically on TPU at this length)
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu import optimizer as opt, jit

    pt.seed(0)
    cfg = BertConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, intermediate_size=128,
                     max_position_embeddings=1024, use_recompute=True)
    m = BertForPretraining(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    ids = rng.randint(0, 512, (1, 1024)).astype("i4")
    mlm = np.where(rng.rand(1, 1024) < 0.15,
                   rng.randint(0, 512, (1, 1024)), -1).astype("i4")
    nsp = np.zeros((1,), "i4")

    def step(i, ml, ns):
        lo, nl = m(i)
        loss = m.loss(lo, nl, ml, ns)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    f = jit.to_static(step, models=[m], optimizers=[o])
    args = [pt.to_tensor(a) for a in (ids, mlm, nsp)]
    losses = [float(f(*args).numpy()) for _ in range(3)]
    print(f"seq-1024 recompute BERT: loss {losses[0]:.4f} -> "
          f"{losses[-1]:.4f}")


if __name__ == "__main__":
    main()
