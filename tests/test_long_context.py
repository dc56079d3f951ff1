"""Long-context pieces working together: flash kernel at longer seq,
recompute through the encoder, ring attention on the sp mesh (SURVEY §2
row 30)."""
import numpy as np

import paddle_tpu as pt
from paddle_tpu.ops.pallas import flash_attention


def test_flash_longer_seq_causal_matches_sdpa():
    from paddle_tpu.nn import functional as F
    rng = np.random.RandomState(0)
    b, h, s, d = 1, 2, 256, 32
    q = rng.randn(b, h, s, d).astype("f4")
    k = rng.randn(b, h, s, d).astype("f4")
    v = rng.randn(b, h, s, d).astype("f4")
    out = flash_attention(pt.to_tensor(q), pt.to_tensor(k),
                          pt.to_tensor(v), causal=True, block_q=128,
                          block_k=128, force=True)
    ref = F.scaled_dot_product_attention(
        pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v), is_causal=True)
    np.testing.assert_allclose(out.numpy(), ref.numpy(), atol=2e-3)


def test_bert_long_seq_recompute_flash_trains():
    """Tiny-width BERT at seq 512 with recompute on: the long-context
    configuration (flash stays off on CPU via the auto gate — it runs on
    TPU; recompute is exercised for real)."""
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    from paddle_tpu import optimizer as opt, jit

    pt.seed(0)
    cfg = BertConfig(vocab_size=128, hidden_size=32, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=64,
                     max_position_embeddings=512, use_recompute=True)
    m = BertForPretraining(cfg)
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters())
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 128, (1, 512)).astype("i4")
    mlm = np.where(rng.rand(1, 512) < 0.15,
                   rng.randint(0, 128, (1, 512)), -1).astype("i4")
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
    losses = [float(f(*args).numpy()) for _ in range(4)]
    assert losses[-1] < losses[0]


def test_ring_attention_causal_matches_full():
    """Causal ring attention over sp=4 equals single-device causal
    attention (the long-seq scaling path)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.parallel.ring_attention import _ring_attention_impl

    rng = np.random.RandomState(1)
    b, hd, s, d = 2, 2, 32, 8
    q = rng.randn(b, hd, s, d).astype("f4")
    k = rng.randn(b, hd, s, d).astype("f4")
    v = rng.randn(b, hd, s, d).astype("f4")

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("sp",))
    f = jax.jit(jax.shard_map(
        lambda q, k, v: _ring_attention_impl(q, k, v, "sp", True, None),
        mesh=mesh,
        in_specs=(P(None, None, "sp", None),) * 3,
        out_specs=P(None, None, "sp", None), check_vma=False))
    out = np.asarray(f(q, k, v))

    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    mask = np.tril(np.ones((s, s), bool))
    logits = np.where(mask, logits, -1e30)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(out, ref, atol=2e-4)
