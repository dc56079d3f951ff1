"""Pallas fused kernels vs reference math (interpret mode on CPU,
SURVEY §4)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.ops.pallas import (layer_norm, softmax_cross_entropy,
                                   flash_attention)


def test_layer_norm_forward_matches():
    x = np.random.randn(32, 128).astype("f4")
    w = np.random.rand(128).astype("f4") + 0.5
    b = np.random.randn(128).astype("f4")
    out = layer_norm(pt.to_tensor(x), pt.to_tensor(w), pt.to_tensor(b))
    mu = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    ref = (x - mu) / np.sqrt(var + 1e-5) * w + b
    np.testing.assert_allclose(out.numpy(), ref, atol=1e-4)


def test_layer_norm_grad_matches_xla():
    x = np.random.randn(16, 64).astype("f4")
    w = np.random.rand(64).astype("f4") + 0.5
    b = np.random.randn(64).astype("f4")

    tx = pt.to_tensor(x, stop_gradient=False)
    tw = pt.Parameter(w)
    tb = pt.Parameter(b)
    (layer_norm(tx, tw, tb) * pt.to_tensor(np.arange(64, dtype="f4"))
     ).sum().backward()

    tx2 = pt.to_tensor(x, stop_gradient=False)
    tw2 = pt.Parameter(w)
    tb2 = pt.Parameter(b)
    from paddle_tpu.nn import functional as F
    (F.layer_norm(tx2, 64, tw2, tb2) *
     pt.to_tensor(np.arange(64, dtype="f4"))).sum().backward()

    np.testing.assert_allclose(np.asarray(tx.grad), np.asarray(tx2.grad),
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(tw.grad), np.asarray(tw2.grad),
                               rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(np.asarray(tb.grad), np.asarray(tb2.grad),
                               rtol=2e-3, atol=2e-3)


def test_softmax_xent_matches_and_grads():
    logits = np.random.randn(24, 50).astype("f4")
    labels = np.random.randint(0, 50, (24,))

    t = pt.to_tensor(logits, stop_gradient=False)
    loss = softmax_cross_entropy(t, pt.to_tensor(labels))
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) + \
        logits.max(-1)
    ref = lse - logits[np.arange(24), labels]
    np.testing.assert_allclose(loss.numpy().ravel(), ref, atol=1e-4)

    loss.mean().backward()
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    onehot = np.eye(50, dtype="f4")[labels]
    ref_grad = (p - onehot) / 24
    np.testing.assert_allclose(np.asarray(t.grad), ref_grad, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_sdpa(causal):
    b, h, s, d = 2, 2, 64, 16
    rng = np.random.RandomState(0)
    q = rng.randn(b, h, s, d).astype("f4")
    k = rng.randn(b, h, s, d).astype("f4")
    v = rng.randn(b, h, s, d).astype("f4")
    out = flash_attention(pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v),
                          causal=causal, block_q=32, block_k=32, force=True)
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    if causal:
        mask = np.tril(np.ones((s, s), bool))
        logits = np.where(mask, logits, -1e30)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(out.numpy(), ref, atol=2e-3)


def test_flash_attention_backward():
    b, h, s, d = 1, 2, 32, 8
    rng = np.random.RandomState(1)
    q = pt.to_tensor(rng.randn(b, h, s, d).astype("f4"), stop_gradient=False)
    k = pt.to_tensor(rng.randn(b, h, s, d).astype("f4"), stop_gradient=False)
    v = pt.to_tensor(rng.randn(b, h, s, d).astype("f4"), stop_gradient=False)
    flash_attention(q, k, v, causal=True, block_q=16,
                    block_k=16, force=True).sum().backward()
    from paddle_tpu.nn import functional as F
    q2 = pt.to_tensor(q.numpy(), stop_gradient=False)
    k2 = pt.to_tensor(k.numpy(), stop_gradient=False)
    v2 = pt.to_tensor(v.numpy(), stop_gradient=False)
    F.scaled_dot_product_attention(q2, k2, v2,
                                   is_causal=True).sum().backward()
    np.testing.assert_allclose(np.asarray(q.grad), np.asarray(q2.grad),
                               atol=3e-3)
    np.testing.assert_allclose(np.asarray(k.grad), np.asarray(k2.grad),
                               atol=3e-3)
    np.testing.assert_allclose(np.asarray(v.grad), np.asarray(v2.grad),
                               atol=3e-3)


def test_pallas_layer_norm_layer_flag():
    from paddle_tpu import nn
    ln = nn.LayerNorm(32, use_pallas=True)
    x = pt.to_tensor(np.random.randn(4, 32).astype("f4"))
    out = ln(x)
    o = out.numpy()
    np.testing.assert_allclose(o.mean(-1), 0.0, atol=1e-4)


def test_flash_attention_unaligned_seq():
    """Regression: tail K/V block must not be dropped (seq % block_k != 0)."""
    b, h, s, d = 1, 2, 40, 16
    rng = np.random.RandomState(3)
    q = rng.randn(b, h, s, d).astype("f4")
    k = rng.randn(b, h, s, d).astype("f4")
    v = rng.randn(b, h, s, d).astype("f4")
    out = flash_attention(pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v),
                          block_q=32, block_k=32, force=True)
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(out.numpy(), ref, atol=2e-3)


def test_flash_attention_key_mask_fused():
    """Additive key-padding mask ([B,1,1,Sk], the BERT shape) is fused into
    the kernel and matches sdpa exactly (VERDICT r2 #1)."""
    b, h, s, d = 2, 2, 32, 8
    rng = np.random.RandomState(5)
    q = rng.randn(b, h, s, d).astype("f4")
    k = rng.randn(b, h, s, d).astype("f4")
    v = rng.randn(b, h, s, d).astype("f4")
    m = np.where(rng.rand(b, 1, 1, s) < 0.3, -1e9, 0.0).astype("f4")
    out = flash_attention(pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v),
                          attn_mask=pt.to_tensor(m), block_q=16,
                          block_k=16, force=True)
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d) + m
    e = np.exp(logits - logits.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, v)
    np.testing.assert_allclose(out.numpy(), ref, atol=2e-3)


def test_flash_attention_key_mask_grads():
    """Backward through the fused key-mask path (the default BERT path)
    matches sdpa — guards the (1,BK) key-bias broadcast in the backward
    kernel."""
    b, h, s, d = 2, 2, 24, 8
    rng = np.random.RandomState(9)
    qn = rng.randn(b, h, s, d).astype("f4")
    kn = rng.randn(b, h, s, d).astype("f4")
    vn = rng.randn(b, h, s, d).astype("f4")
    mn = np.where(rng.rand(b, 1, 1, s) < 0.3, -1e9, 0.0).astype("f4")
    q = pt.to_tensor(qn, stop_gradient=False)
    k = pt.to_tensor(kn, stop_gradient=False)
    v = pt.to_tensor(vn, stop_gradient=False)
    flash_attention(q, k, v, attn_mask=pt.to_tensor(mn), block_q=16,
                    block_k=16, force=True).sum().backward()
    from paddle_tpu.nn import functional as F
    q2 = pt.to_tensor(qn, stop_gradient=False)
    k2 = pt.to_tensor(kn, stop_gradient=False)
    v2 = pt.to_tensor(vn, stop_gradient=False)
    F.scaled_dot_product_attention(
        q2, k2, v2, attn_mask=pt.to_tensor(mn)).sum().backward()
    for a, bb in ((q, q2), (k, k2), (v, v2)):
        np.testing.assert_allclose(np.asarray(a.grad), np.asarray(bb.grad),
                                   atol=3e-3)


def test_flash_attention_fully_masked_row_grads():
    """Regression (review r3): rows whose every visible key carries a
    finite -1e9 bias must still produce sdpa-matching gradients — the
    backward reconstructs p from (m, l), not the folded lse, so 1e9-scale
    scores round identically to the forward."""
    b, h, s, d = 1, 1, 24, 8
    rng = np.random.RandomState(10)
    qn = rng.randn(b, h, s, d).astype("f4")
    kn = rng.randn(b, h, s, d).astype("f4")
    vn = rng.randn(b, h, s, d).astype("f4")
    mn = np.zeros((1, 1, s, s), "f4")
    mn[0, 0, 3, :] = -1e9   # row 3 fully masked (finite bias, not -inf)
    mn[0, 0, 7, :20] = -1e9  # row 7 nearly fully masked
    q = pt.to_tensor(qn, stop_gradient=False)
    k = pt.to_tensor(kn, stop_gradient=False)
    v = pt.to_tensor(vn, stop_gradient=False)
    flash_attention(q, k, v, attn_mask=pt.to_tensor(mn),
                    block_q=8, block_k=8, force=True).sum().backward()
    from paddle_tpu.nn import functional as F
    q2 = pt.to_tensor(qn, stop_gradient=False)
    k2 = pt.to_tensor(kn, stop_gradient=False)
    v2 = pt.to_tensor(vn, stop_gradient=False)
    F.scaled_dot_product_attention(
        q2, k2, v2, attn_mask=pt.to_tensor(mn)).sum().backward()
    for a, bb in ((q, q2), (k, k2), (v, v2)):
        np.testing.assert_allclose(np.asarray(a.grad), np.asarray(bb.grad),
                                   atol=3e-3)


def test_flash_attention_full_mask_grads():
    """Full [1,1,Sq,Sk] additive mask: forward + grads match sdpa."""
    b, h, s, d = 1, 2, 24, 8
    rng = np.random.RandomState(6)
    qn = rng.randn(b, h, s, d).astype("f4")
    kn = rng.randn(b, h, s, d).astype("f4")
    vn = rng.randn(b, h, s, d).astype("f4")
    mn = (rng.randn(1, 1, s, s) * 2).astype("f4")
    q = pt.to_tensor(qn, stop_gradient=False)
    k = pt.to_tensor(kn, stop_gradient=False)
    v = pt.to_tensor(vn, stop_gradient=False)
    flash_attention(q, k, v, attn_mask=pt.to_tensor(mn), block_q=16,
                    block_k=16, force=True).sum().backward()
    from paddle_tpu.nn import functional as F
    q2 = pt.to_tensor(qn, stop_gradient=False)
    k2 = pt.to_tensor(kn, stop_gradient=False)
    v2 = pt.to_tensor(vn, stop_gradient=False)
    F.scaled_dot_product_attention(
        q2, k2, v2, attn_mask=pt.to_tensor(mn)).sum().backward()
    for a, bb in ((q, q2), (k, k2), (v, v2)):
        np.testing.assert_allclose(np.asarray(a.grad), np.asarray(bb.grad),
                                   atol=3e-3)


def _masked_row_case(kind, b, h, s, rng):
    """(attn_mask or None, causal) with one query row — a whole batch
    element for a key mask — whose every key carries the finite -1e9."""
    if kind == "key":
        m = np.where(rng.rand(b, 1, 1, s) < 0.3, -1e9, 0.0).astype("f4")
        m[1] = -1e9                     # batch element 1: every key masked
        return m, False
    if kind == "full":
        m = (rng.randn(1, 1, s, s) * 2).astype("f4")
        m[0, 0, 19, :] = -1e9           # a row in the middle q-block
        m[0, 0, s - 1, :s - 2] = -1e9   # and the last row, nearly
        return m, False
    return None, True                   # causal, no mask


@pytest.mark.parametrize("kind", ["key", "full", "causal"])
def test_flash_row_statistics_one_value_a_row(kind):
    """The soft-max row statistics cross from the forward to the
    backward kernel as one value a (batch*head, row), sequence on the
    lane axis. Several q-blocks AND k-blocks, seq not a multiple of the
    block, a fully masked row: where a relaid statistic could land on
    the wrong row, output, statistics and dQ/dK/dV must still be the
    sdpa path's."""
    import jax.numpy as jnp
    from paddle_tpu.nn import functional as F
    from paddle_tpu.ops.pallas.flash_attention import (_canon_mask, _fwd,
                                                       _mask_mode)
    b, h, s, d, blk = 2, 2, 40, 8, 16   # 2.5 blocks each way
    rng = np.random.RandomState(26)
    qn, kn, vn, ct = (rng.randn(b, h, s, d).astype("f4") for _ in range(4))
    mn, causal = _masked_row_case(kind, b, h, s, rng)

    # what _fwd saves for the backward: shapes, then values
    mode = _mask_mode(None if mn is None else mn.shape, b, h, s, s)
    assert mode == (None if mn is None else kind)
    mask = None if mn is None else _canon_mask(jnp.asarray(mn))
    out, res = _fwd(jnp.asarray(qn), jnp.asarray(kn), jnp.asarray(vn), mask,
                    mode, jnp.zeros((2,), jnp.int32), causal, None, blk, blk,
                    0.0)
    mrow, lrow = res[6], res[7]
    assert mrow.shape == lrow.shape == (b * h, 1, s)
    assert mrow.dtype == lrow.dtype == jnp.float32
    # float32 throughout: beside the -1e9 bias a score rounds to a
    # multiple of 64 in the kernel and in sdpa alike
    logits = np.einsum("bhqd,bhkd->bhqk", qn, kn) / np.float32(np.sqrt(d))
    if mn is not None:
        logits = logits + mn
    if causal:
        logits = np.where(np.tril(np.ones((s, s), bool)), logits,
                          np.float32(-np.inf))
    assert logits.dtype == np.float32
    m_ref = logits.max(-1)
    l_ref = np.exp(logits - m_ref[..., None]).sum(-1)
    np.testing.assert_allclose(np.asarray(mrow).reshape(b, h, s), m_ref,
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(lrow).reshape(b, h, s), l_ref,
                               rtol=2e-3)

    # forward and gradients through the op, against sdpa
    am = None if mn is None else pt.to_tensor(mn)
    q, k, v = (pt.to_tensor(a, stop_gradient=False) for a in (qn, kn, vn))
    o = flash_attention(q, k, v, attn_mask=am, causal=causal, block_q=blk,
                        block_k=blk, force=True)
    np.testing.assert_allclose(o.numpy(), np.asarray(out), atol=1e-6)
    (o * pt.to_tensor(ct)).sum().backward()
    q2, k2, v2 = (pt.to_tensor(a, stop_gradient=False) for a in (qn, kn, vn))
    o2 = F.scaled_dot_product_attention(q2, k2, v2, attn_mask=am,
                                        is_causal=causal)
    (o2 * pt.to_tensor(ct)).sum().backward()
    np.testing.assert_allclose(o.numpy(), o2.numpy(), atol=2e-3)
    for a, bb in ((q, q2), (k, k2), (v, v2)):
        np.testing.assert_allclose(np.asarray(a.grad), np.asarray(bb.grad),
                                   atol=3e-3)


def test_flash_attention_dropout_fused():
    """Attention dropout is fused in-kernel: deterministic per seed,
    seed-sensitive, output stays correctly scaled (VERDICT r2 #1 — the
    old sdpa fallback under dropout is gone)."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import _flash
    b, h, s, d = 1, 2, 16, 8
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    s1 = jnp.asarray([1, 2], jnp.int32)
    s2 = jnp.asarray([3, 4], jnp.int32)
    o1 = _flash(q, k, v, None, None, s1, False, None, 16, 16, 0.4)
    o1b = _flash(q, k, v, None, None, s1, False, None, 16, 16, 0.4)
    o2 = _flash(q, k, v, None, None, s2, False, None, 16, 16, 0.4)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o1b))
    assert np.abs(np.asarray(o1) - np.asarray(o2)).max() > 1e-4


def test_flash_attention_dropout_grad_finite_difference():
    """The fused backward regenerates the identical dropout mask: custom
    VJP matches finite differences (mask is fixed given the seed)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flash_attention import _flash
    b, h, s, d = 1, 1, 16, 8
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    w = jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
    seed = jnp.asarray([5, 6], jnp.int32)

    def f(q, k, v):
        return jnp.sum(_flash(q, k, v, None, None, seed, False, None, 16,
                              16, 0.3) * w)

    gq, gk, gv = jax.grad(f, (0, 1, 2))(q, k, v)
    eps, i = 1e-3, (0, 0, 3, 5)
    for arr, g, which in ((q, gq, "q"), (k, gk, "k"), (v, gv, "v")):
        args = {"q": [arr if which == "q" else q, k, v],
                "k": [q, arr if which == "k" else k, v],
                "v": [q, k, arr if which == "v" else v]}[which]
        idx = {"q": 0, "k": 1, "v": 2}[which]
        plus = list(args)
        plus[idx] = args[idx].at[i].add(eps)
        minus = list(args)
        minus[idx] = args[idx].at[i].add(-eps)
        fd = (f(*plus) - f(*minus)) / (2 * eps)
        np.testing.assert_allclose(float(fd), float(g[i]), rtol=5e-2,
                                   atol=5e-3)


def test_flash_wrapper_dropout_no_fallback_shape():
    b, h, s, d = 1, 1, 16, 8
    q = pt.to_tensor(np.random.randn(b, h, s, d).astype("f4"))
    out = flash_attention(q, q, q, dropout_p=0.5, training=True,
                          block_q=16, block_k=16, force=True)
    assert out.shape == [b, h, s, d]


def test_layer_norm_multiblock_rows():
    """Row count spanning several blocks incl. a partial final block; the
    bwd dw/db accumulation must not double-count or include padding."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.layer_norm import _layer_norm2
    rng = np.random.RandomState(2)
    d = 768
    n = 683 * 2 + 11  # > 2 blocks at the 512K-element target for d=768
    x = rng.randn(n, d).astype("f4")
    w = rng.randn(d).astype("f4")
    b = rng.randn(d).astype("f4")

    def ref(x, w, b):
        mu = x.mean(1, keepdims=True)
        var = x.var(1, keepdims=True)
        return (x - mu) / np.sqrt(var + 1e-5) * w + b

    out = _layer_norm2(jnp.asarray(x), jnp.asarray(w), jnp.asarray(b), 1e-5)
    np.testing.assert_allclose(np.asarray(out), ref(x, w, b), atol=2e-4)

    def f(x, w, b):
        # all-ones cotangent: the analytic dw/db checks below assume it
        return _layer_norm2(x, w, b, 1e-5).sum()

    gx, gw, gb = jax.grad(f, argnums=(0, 1, 2))(
        jnp.asarray(x), jnp.asarray(w), jnp.asarray(b))
    # dw/db vs analytic: db = sum(g) = n per feature? g == 1 everywhere
    xn = (x - x.mean(1, keepdims=True)) / np.sqrt(
        x.var(1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(gb), np.full(d, float(n)),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), xn.sum(0), atol=2e-2)


def test_pallas_configure_overrides():
    """pallas.configure() flips the auto defaults consulted at forward/
    step time (the bench probe uses this to degrade one kernel at a
    time)."""
    from paddle_tpu.ops import pallas as P
    try:
        assert P.enabled("layer_norm") == P.on_tpu()
        P.configure(layer_norm=True, softmax_xent=True)
        assert P.enabled("layer_norm") is True
        assert P.enabled("softmax_xent") is True
        # a LayerNorm built BEFORE the configure() call still honors it
        from paddle_tpu import nn
        ln = nn.LayerNorm(16)
        x = pt.to_tensor(np.random.RandomState(0).randn(4, 16).astype("f4"))
        out_forced = ln(x).numpy()  # interpret-mode pallas on CPU
        P.configure(layer_norm=False)
        out_xla = ln(x).numpy()
        np.testing.assert_allclose(out_forced, out_xla, atol=1e-5)
    finally:
        P.configure(layer_norm=None, softmax_xent=None)
        # None restores the auto defaults: layer_norm is auto-on on
        # TPU, softmax_xent auto-off everywhere (docs/performance.md)
        assert P.enabled("layer_norm") == P.on_tpu()
        assert P.enabled("softmax_xent") is False


def test_softmax_xent_gated_in_loss_op():
    """softmax_with_cross_entropy routes through the fused kernel when
    configure(softmax_xent=True); numerics (incl. ignore_index masking
    and grads) must match the XLA logsumexp path."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas as P
    from paddle_tpu.ops.loss import softmax_with_cross_entropy
    rng = np.random.RandomState(3)
    logits = rng.randn(6, 128, 33).astype("f4")
    label = rng.randint(0, 33, (6, 128)).astype("i4")
    label[0, :7] = -1  # ignored positions

    def run():
        x = pt.to_tensor(logits.copy())
        x.stop_gradient = False
        loss = softmax_with_cross_entropy(x, pt.to_tensor(label),
                                          ignore_index=-1)
        loss.sum().backward()
        return loss.numpy(), np.asarray(x.grad)

    try:
        P.configure(softmax_xent=True)
        l_k, g_k = run()
    finally:
        P.configure(softmax_xent=None)
    P.configure(softmax_xent=False)
    try:
        l_x, g_x = run()
    finally:
        P.configure(softmax_xent=None)
    np.testing.assert_allclose(l_k, l_x, atol=1e-4)
    np.testing.assert_allclose(g_k, g_x, atol=1e-4)


@pytest.mark.parametrize("name", [
    "flash_atention",    # a typo must not pass silently
    # kernels that are gone (PR 29): a script that still asks for one
    # is told so, not left believing it measured a kernel
    "fused_adam", "fused_adam_multi"])
def test_pallas_configure_rejects_unknown(name):
    from paddle_tpu.ops import pallas as P
    with pytest.raises(ValueError):
        P.configure(**{name: False})
    with pytest.raises(ValueError):
        P.enabled(name)


def test_softmax_xent_gated_in_cross_entropy():
    """cross_entropy (the flagship BERT loss path) routes through the
    fused kernel too; mean-reduction over non-ignored rows, weights, and
    grads must match the XLA path."""
    import jax
    from paddle_tpu.ops import pallas as P
    from paddle_tpu.ops.loss import cross_entropy
    rng = np.random.RandomState(4)
    logits = rng.randn(5, 64, 17).astype("f4")
    label = rng.randint(0, 17, (5, 64)).astype("i4")
    label[1, :9] = -1

    def run(weight=None):
        x = pt.to_tensor(logits.copy())
        x.stop_gradient = False
        loss = cross_entropy(x, pt.to_tensor(label), ignore_index=-1,
                             weight=weight)
        loss.backward()
        return float(loss.numpy()), np.asarray(x.grad)

    w = pt.to_tensor(rng.rand(17).astype("f4") + 0.5)
    try:
        P.configure(softmax_xent=True)
        l_k, g_k = run()
        lw_k, gw_k = run(weight=w)
    finally:
        P.configure(softmax_xent=None)
    P.configure(softmax_xent=False)
    try:
        l_x, g_x = run()
        lw_x, gw_x = run(weight=w)
    finally:
        P.configure(softmax_xent=None)
    np.testing.assert_allclose(l_k, l_x, rtol=1e-5)
    np.testing.assert_allclose(g_k, g_x, atol=1e-5)
    np.testing.assert_allclose(lw_k, lw_x, rtol=1e-5)
    np.testing.assert_allclose(gw_k, gw_x, atol=1e-5)


def test_softmax_xent_label_smoothing():
    """Smoothed kernel path == label_smooth + soft-label XLA path (loss
    and grads), incl. through Transformer.loss gating."""
    import jax
    from paddle_tpu.ops import pallas as P
    from paddle_tpu.ops import loss as L, one_hot
    from paddle_tpu.nn import functional as F
    rng = np.random.RandomState(7)
    eps = 0.1
    logits = rng.randn(4, 20, 29).astype("f4")
    labels = rng.randint(0, 29, (4, 20)).astype("i4")

    x1 = pt.to_tensor(logits.copy())
    x1.stop_gradient = False
    loss1 = P.softmax_cross_entropy(x1, pt.to_tensor(labels),
                                    smooth_eps=eps)
    loss1.sum().backward()

    x2 = pt.to_tensor(logits.copy())
    x2.stop_gradient = False
    soft = F.label_smooth(one_hot(pt.to_tensor(labels), 29), epsilon=eps)
    loss2 = L.softmax_with_cross_entropy(x2, soft, soft_label=True)
    loss2.sum().backward()

    np.testing.assert_allclose(loss1.numpy(), loss2.numpy(), atol=1e-4)
    np.testing.assert_allclose(np.asarray(x1.grad), np.asarray(x2.grad),
                               atol=1e-5)


def test_transformer_loss_pallas_gate():
    """Force the softmax_xent gate on: Transformer.loss through the fused
    smoothed kernel must match its own XLA fallback path."""
    from paddle_tpu.ops import pallas as P
    from paddle_tpu.models.transformer import Transformer

    pt.seed(0)
    model = Transformer(src_vocab_size=37, tgt_vocab_size=37, d_model=16,
                        num_heads=2, d_ff=32, num_encoder_layers=1,
                        num_decoder_layers=1)
    rng = np.random.RandomState(8)
    logits = pt.to_tensor(rng.randn(2, 9, 37).astype("f4"))
    labels = pt.to_tensor(rng.randint(0, 37, (2, 9)).astype("i4"))
    try:
        P.configure(softmax_xent=True)
        l_k = float(model.loss(logits, labels).numpy())
    finally:
        P.configure(softmax_xent=None)
    P.configure(softmax_xent=False)
    try:
        l_x = float(model.loss(logits, labels).numpy())
    finally:
        P.configure(softmax_xent=None)
    np.testing.assert_allclose(l_k, l_x, rtol=1e-5)


def test_flash_min_seq_gate():
    """configure(flash_min_seq=N) routes short sequences to sdpa even
    with the kernel force-enabled (the ablation-tuned crossover knob)."""
    from paddle_tpu.ops import pallas as P
    import numpy as np
    import paddle_tpu as pt

    q = pt.to_tensor(np.random.RandomState(0).randn(1, 2, 16, 8)
                     .astype("f4"))
    try:
        P.configure(flash_attention=True, flash_min_seq=64)
        assert not P.enabled("flash_attention", seq_len=16)
        assert P.enabled("flash_attention", seq_len=128)
        # short seq runs through the sdpa fallback (no interpret-mode
        # kernel = fast) and matches plain attention
        out = P.flash_attention(q, q, q)
        from paddle_tpu.ops.nn_ops import scaled_dot_product_attention
        ref = scaled_dot_product_attention(q, q, q, training=False)
        np.testing.assert_allclose(out.numpy(), ref.numpy(), rtol=1e-5,
                                   atol=1e-6)
    finally:
        P.configure(flash_attention=None, flash_min_seq=None)


def test_fused_batch_norm_parity_and_grads():
    """Pallas fused BN (interpret mode) vs the XLA batch_norm path:
    forward, batch stats, running-stat update, and grads w.r.t.
    x/weight/bias must match. M=200 deliberately not a multiple of the
    row block so the masked tail is exercised."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.batch_norm import _batch_norm2

    rng = np.random.RandomState(0)
    m, c = 200, 24
    x = jnp.asarray(rng.randn(m, c).astype("f4") * 2 + 3)
    w = jnp.asarray(rng.rand(c).astype("f4") + 0.5)
    b = jnp.asarray(rng.randn(c).astype("f4"))
    g = jnp.asarray(rng.randn(m, c).astype("f4"))

    def ref(x, w, b, eps=1e-5):
        mean = jnp.mean(x, axis=0)
        var = jnp.var(x, axis=0)
        inv = jax.lax.rsqrt(var + eps)
        return (x - mean) * inv * w + b, mean, var

    out, mean, var = _batch_norm2(x, w, b, 1e-5)
    r_out, r_mean, r_var = ref(x, w, b)
    np.testing.assert_allclose(out, r_out, atol=2e-4)
    np.testing.assert_allclose(np.asarray(mean).ravel(), r_mean, atol=1e-4)
    np.testing.assert_allclose(np.asarray(var).ravel(), r_var, atol=2e-3)

    # grads through out only (the usual training path)
    g1 = jax.grad(lambda *a: jnp.sum(_batch_norm2(*a, 1e-5)[0] * g),
                  argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(lambda *a: jnp.sum(ref(*a)[0] * g),
                  argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(g1, g2):
        np.testing.assert_allclose(a, r, atol=3e-4)

    # grads through the DIRECT mean/var outputs stay exact too
    gm = jnp.asarray(rng.randn(c).astype("f4"))
    gv = jnp.asarray(rng.randn(c).astype("f4"))

    def take_stats(f):
        def inner(x):
            _, mean, var = f(x, w, b) if f is not _batch_norm2 else \
                f(x, w, b, 1e-5)
            return jnp.sum(mean * gm) + jnp.sum(var * gv)
        return inner

    ga = jax.grad(take_stats(_batch_norm2))(x)
    gr = jax.grad(take_stats(ref))(x)
    np.testing.assert_allclose(ga, gr, atol=3e-4)

    # large-mean regime: the sample-shifted accumulators must keep the
    # variance (raw E[x^2]-E[x]^2 loses it entirely at mean ~1e3)
    xl = jnp.asarray(rng.randn(m, c).astype("f4") + 1000.0)
    out_l, _, var_l = _batch_norm2(xl, w, b, 1e-5)
    np.testing.assert_allclose(np.asarray(var_l).ravel(),
                               jnp.var(xl, axis=0), rtol=0.05)
    assert abs(float(jnp.mean((out_l - b) / w))) < 0.1
    assert 0.8 < float(jnp.std((out_l - b) / w)) < 1.2


def test_fused_batch_norm_gated_in_layer():
    """configure(batch_norm=True) routes a channels-last BatchNorm1D
    through the Pallas kernel; training numerics (incl. running-stat
    carry) must match the XLA path, and NCHW inputs must keep the XLA
    path (no silent transpose)."""
    from paddle_tpu.ops import pallas as P
    from paddle_tpu import nn

    rng = np.random.RandomState(1)
    x = rng.randn(32, 12).astype("f4")

    def run(use):
        import paddle_tpu as pt
        pt.seed(0)
        P.configure(batch_norm=use)
        try:
            bn = nn.BatchNorm1D(12, data_format="NLC")
            bn.train()
            out = bn(pt.to_tensor(x))
            loss = (out ** 2).mean()
            loss.backward()
            return (out.numpy(), bn._mean.numpy(), bn._variance.numpy(),
                    np.asarray(bn.weight.grad))
        finally:
            P.configure(batch_norm=None)

    o1 = run(True)
    o2 = run(False)
    for a, b_ in zip(o1, o2):
        np.testing.assert_allclose(a, b_, atol=3e-4)


