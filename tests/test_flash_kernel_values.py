"""The flash kernels' values (ops/pallas/flash_attention.py): bfloat16
output and gradients of the two kernels, interpreted on the CPU, against
plain float32 attention over a grid of block shapes, head sizes, masks and
dropout, at lengths of whole blocks and of two and a half, at sq != sk, and
under the block-diffusion structure. What the kernels' jaxprs hold and what
the dispatch counts is read in tests/test_flash_kernel_loop.py. CPU only:
values, no time."""
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import (_canon_mask, _flash,
                                                   _flash_bd,
                                                   _host_keep_mask,
                                                   _mask_mode,
                                                   block_diffusion_mask)

B, H, D, DV = 1, 2, 24, 16


def _reference(q, k, v, ct, bias, causal, keep, p_drop):
    """Plain float32 attention and its gradients; ``keep`` is the
    dropout's (B*H, Sq, Sk) keep-mask or None."""
    b, h, sq, d = q.shape
    sk = k.shape[2]

    def f(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.float32(np.sqrt(d))
        if bias is not None:
            s = s + bias
        if causal:
            s = jnp.where(np.tril(np.ones((sq, sk), bool)), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if keep is not None:
            p = p * keep.reshape(b, h, sq, sk) / (1.0 - p_drop)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    out, vjp = jax.vjp(f, q, k, v)
    return (out,) + vjp(ct)


def _bias(kind, seq, rng, sk=None):
    sk = seq if sk is None else sk
    if kind == "none":
        return None
    if kind == "key":
        return np.where(rng.rand(B, 1, 1, sk) < 0.3, -1e9, 0.0).astype("f4")
    m = (rng.randn(1, 1, seq, sk) * 2).astype("f4")
    if kind == "masked_rows":
        m[0, 0, 3, :] = -1e9            # a row with every key masked
        m[0, 0, seq - 1, :seq - 2] = -1e9   # and the last row, nearly
        m[0, 0, 19, :] = -1e9
    return m


CASES = list(itertools.product(
    (False, True),                          # causal
    ("aligned", "2.5 blocks"),
    ((16, 16), (16, 32)),                   # BQ = BK | BQ < BK
    ((16, 16), (24, 16)),                   # head sizes d, dv
    ("none", "key", "full", "masked_rows"),
    (0.0, 0.1)))                            # dropout
# BQ > BK: two crossed tiles a q-block in the forward kernel
CASES += list(itertools.product((True,), ("aligned", "2.5 blocks"),
                                ((32, 16),), ((24, 16),), ("none", "key"),
                                (0.0,)))


@pytest.mark.parametrize(
    "causal,lengths,blocks,heads,mask,p_drop", CASES,
    ids=["-".join(("causal" if c[0] else "bidir", c[1].replace(" ", ""),
                   "%dx%d" % c[2], "d%d.%d" % c[3], c[4], "drop%g" % c[5]))
         for c in CASES])
def test_bf16_kernels_against_float32_attention(causal, lengths, blocks,
                                                heads, mask, p_drop):
    seq = 2 * blocks[1] if lengths == "aligned" else 5 * blocks[1] // 2
    _against_float32_attention(
        causal, seq, seq, blocks, heads, mask, p_drop,
        len(str((causal, lengths, blocks, heads, mask, p_drop))))


# sq != sk (the diagonal starts at the first row and the first key): a q
# side of 2.5 blocks against 4.5 k-blocks and the other way round, so a
# k-block's walk ends, or starts, where the other side's rows do; every
# k-block of a head adds its part to the one dq accumulator
CROSS = list(itertools.product(
    (False, True), ((40, 72), (72, 40)), ("none", "key", "full"),
    (0.0, 0.1)))


@pytest.mark.parametrize(
    "causal,lengths,mask,p_drop", CROSS,
    ids=["-".join(("causal" if c[0] else "bidir", "q%d.k%d" % c[1], c[2],
                   "drop%g" % c[3])) for c in CROSS])
def test_bf16_kernels_against_float32_attention_at_sq_not_sk(causal, lengths,
                                                             mask, p_drop):
    _against_float32_attention(causal, *lengths, (16, 16), (24, 16), mask,
                               p_drop, len(str((causal, mask, p_drop))))


def _against_float32_attention(causal, seq, sk, blocks, heads, mask, p_drop,
                               salt):
    (bq, bk), (d, dv) = blocks, heads
    rng = np.random.RandomState(salt + seq + d)
    # bfloat16 inputs; the reference reads the same rounded numbers
    q, k, v, ct = (jnp.asarray(rng.randn(B, H, n, w), jnp.bfloat16)
                   for n, w in ((seq, d), (sk, d), (sk, dv), (seq, dv)))
    bias = _bias(mask, seq, rng, sk)
    mode = _mask_mode(None if bias is None else bias.shape, B, H, seq, sk)
    assert mode == {"none": None, "key": "key"}.get(mask, "full")
    seed = jnp.asarray([7, 11], jnp.int32)
    canon = None if bias is None else _canon_mask(jnp.asarray(bias))

    def f(q, k, v):
        return _flash(q, k, v, canon, mode, seed, causal, None, bq, bk,
                      p_drop)

    out, vjp = jax.vjp(f, q, k, v)
    got = (out,) + vjp(ct)
    keep = None
    if p_drop:
        pad = lambda n, blk: -(-n // blk) * blk
        keep = _host_keep_mask(seed, B * H, pad(seq, bq), pad(sk, bk),
                               p_drop)[:, :seq, :sk]
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    want = _reference(f32(q), f32(k), f32(v), f32(ct), bias, causal, keep,
                      p_drop)
    for name, a, w in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.dtype == jnp.bfloat16
        a, w = np.asarray(f32(a)), np.asarray(w)
        assert np.isfinite(a).all(), name
        # bfloat16 products and a bfloat16 result: 2^-8 of the array's size
        np.testing.assert_allclose(a, w, atol=0.03 * max(1.0, np.abs(w).max()),
                                   err_msg=name)
        assert np.linalg.norm(a - w) <= 0.012 * np.linalg.norm(w), name


@pytest.mark.parametrize("length,block,bq,bk", [
    (64, 4, 16, 16), (40, 4, 16, 16), (64, 4, 16, 32), (64, 4, 32, 16),
    (64, 16, 16, 16)], ids=lambda n: str(n))
def test_bf16_kernels_under_the_block_structure(length, block, bq, bk):
    """Two copies of ``length`` rows: the backward runs every clean k-block
    twice, and each copy's dq has the accumulator for half the grid."""
    rng = np.random.RandomState(length + block + bq + 2 * bk)
    q, k = (jnp.asarray(rng.randn(B, H, 2 * length, D), jnp.bfloat16)
            for _ in range(2))
    v, ct = (jnp.asarray(rng.randn(B, H, 2 * length, DV), jnp.bfloat16)
             for _ in range(2))

    def f(q, k, v):
        return _flash_bd(q, k, v, block.bit_length() - 1, None, bq, bk)

    out, vjp = jax.vjp(f, q, k, v)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    allowed = np.where(block_diffusion_mask(length, block), 0.0,
                       -np.inf).astype("f4")[None, None]
    want = _reference(f32(q), f32(k), f32(v), f32(ct), allowed, False, None,
                      0.0)
    for name, a, w in zip(("o", "dq", "dk", "dv"), (out,) + vjp(ct), want):
        assert a.dtype == jnp.bfloat16
        a, w = np.asarray(f32(a)), np.asarray(w)
        assert np.isfinite(a).all(), name
        np.testing.assert_allclose(a, w, atol=0.03 * max(1.0, np.abs(w).max()),
                                   err_msg=name)
        assert np.linalg.norm(a - w) <= 0.012 * np.linalg.norm(w), name
