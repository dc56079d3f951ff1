"""The flash kernels under the block-diffusion structure
(``flash_attention(diffusion_block=B)``, kernels ``flash_bd_fwd`` /
``flash_bd_bwd``; the sdar cell's attention over a noisy and a clean copy):
the three-part mask as the written rule, the kernels in interpret mode
against dense masked attention, the tile counts against a brute-force count
and at the cell's shape, the dispatch's counters, and what a call without
the structure still traces. Kernels and counts on the CPU; no model is
built here (tests/test_sdar_moe.py has the model)."""
import hashlib
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                         # noqa: E402
from paddle_tpu import monitor                                  # noqa: E402
from paddle_tpu.ops.pallas import flash_attention               # noqa: E402
from paddle_tpu.ops.pallas import flash_attention_mod as flash_mod  # noqa: E402,E501
from benchmark.reference import sdar_moe as R                   # noqa: E402

# -- the mask ----------------------------------------------------------------

def test_the_mask_is_the_written_rule_on_every_pair():
    """L = 16, B = 4: every (r, s) of the 32 x 32 pairs against the rule
    as ISSUE 33 writes it, and the reference's own."""
    length, block = 16, 4
    got = flash_mod.block_diffusion_mask(length, block)
    assert got.shape == (32, 32) and got.dtype == np.bool_
    for r in range(32):
        for s in range(32):
            c_r, c_s = r // length, s // length
            b_r, b_s = (r % length) // block, (s % length) // block
            want = (c_s == 1 and b_s < b_r) or (c_s == c_r and b_s == b_r)
            assert got[r, s] == want, (r, s)
    at = jnp.arange(32)
    np.testing.assert_array_equal(got, R.allowed(at, at, length, block))
    # a clean row: block-causal over the clean copy, nothing of the noisy
    assert got[16 + 5].tolist() == [False] * 16 + [True] * 8 + [False] * 8
    # a noisy row: the clean blocks before its own, its own noisy block
    assert got[5].tolist() == [False] * 4 + [True] * 4 + [False] * 8 \
        + [True] * 4 + [False] * 12
    assert int(got.sum()) == length * length + length * block


# -- the kernels under the structure ----------------------------------------

def _dense(q, k, v, length, block):
    mask = jnp.asarray(flash_mod.block_diffusion_mask(length, block))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


KERNEL_CASES = [      # (L, B, block_q, block_k)
    (32, 4, 16, 16),       # L a multiple of the tile
    (40, 4, 16, 16),       # ... and not: each copy is padded to 48
    (64, 32, 32, 32),      # a diffusion block is a tile
    (96, 32, 32, 64),      # block_q < block_k, padded to 128
    (48, 4, 16, 8),        # block_q > block_k
    (48, 4, 8, 16),
    (64, 4, 512, 1024),    # the defaults: one tile a copy
]


@pytest.mark.parametrize("length,block,block_q,block_k", KERNEL_CASES)
def test_kernels_under_the_structure_match_dense_masked_attention(
        length, block, block_q, block_k):
    """Interpret mode, float32: forward and all three gradients, q/k 24
    wide and v 16."""
    key = jax.random.key(length * 7 + block)
    q, k, v, ct = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, 2, 2 * length, d))
                   for i, d in enumerate((24, 24, 16, 16)))
    shift = block.bit_length() - 1

    def kernels(q, k, v):
        return flash_mod._flash_bd(q, k, v, shift, None, block_q, block_k)

    np.testing.assert_allclose(kernels(q, k, v),
                               _dense(q, k, v, length, block), atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, length, block) * ct),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-6, err_msg=f"d{name}")


@pytest.mark.parametrize("length,block,block_q,block_k", KERNEL_CASES)
def test_tile_counts_are_a_brute_force_count_of_the_tiles_that_hold_a_pair(
        length, block, block_q, block_k):
    shift = block.bit_length() - 1
    bq, bk = flash_mod._bd_blocks(block_q, block_k, length, shift)
    lp = flash_mod._bd_padded(length, bq, bk)
    tiles, masked, whole = flash_mod._bd_tile_counts(
        3, length, block_q=bq, block_k=bk, shift=shift)
    # the layout the kernels walk: each copy padded to whole tiles
    dense = flash_mod.block_diffusion_mask(lp, block)
    by_tile = dense.reshape(2 * lp // bq, bq, 2 * lp // bk, bk)
    holds = by_tile.any((1, 3))
    assert whole == 3 * holds.size
    assert tiles == 3 * int(holds.sum())
    # over the clean copy's keys a tile that holds a pair and is not all
    # pairs runs the masked body; a noisy block's own tiles always do
    clean_keys = by_tile[:, :, lp // bk:]
    crossed = int((clean_keys.any((1, 3)) & ~clean_keys.all((1, 3))).sum())
    own = (lp // bq) * max(1, bq // bk)
    assert masked == 3 * (crossed + own)


def test_tile_counts_at_the_cells_shape_are_the_issues():
    """32 heads x 2 x 8,192 rows at 512 x 512: n (n + 1) + n of 4 n^2 tiles
    a head, 3 n of them masked, n = 16."""
    bq, bk = flash_mod._blocks_that_fit(8192, 128, 128, 2, 512, 1024)
    assert flash_mod._bd_blocks(bq, bk, 8192, 2) == (512, 512)
    assert not flash_mod._single_buffered(8192, 128, 128, 2)
    tiles, masked, whole = flash_mod._bd_tile_counts(
        32, 8192, block_q=512, block_k=512, shift=2)
    assert (tiles, masked, whole) == (32 * 288, 32 * 48, 32 * 1024)
    assert abs(100 * tiles / whole - 28.125) < 1e-9


def test_the_dispatch_counts_the_path_and_the_tiles_and_refuses_a_mix():
    q = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(3),
                                                  (1, 2, 64, 16))))
    before = monitor.snapshot("flash_attention")
    got = flash_attention(q, q, q, diffusion_block=4, force=True,
                          block_q=16, block_k=16)
    plain = flash_attention(q, q, q, diffusion_block=4)      # sdpa, dense
    np.testing.assert_allclose(got.numpy(), plain.numpy(), atol=2e-6)
    after = monitor.snapshot("flash_attention")

    def gained(name):
        return after.get("flash_attention." + name, 0) \
            - before.get("flash_attention." + name, 0)

    assert gained("kernel_traced") == 1 and gained("xla_traced") == 1
    # two heads, two blocks of 16 a copy: 2 x 3 + 2 of 16 tiles a head
    assert gained("tiles") == 2 * 8 and gained("tiles_masked") == 2 * 6
    assert gained("tiles_skipped") == 2 * 8
    for kw in (dict(causal=True), dict(attn_mask=q), dict(diffusion_block=3),
               dict(diffusion_block=64)):
        with pytest.raises(ValueError, match="diffusion_block"):
            flash_attention(q, q, q, **{"diffusion_block": 4, **kw})
    # a causal call counts what it leaves out too
    before = after
    flash_attention(q, q, q, causal=True, force=True, block_q=16, block_k=16)
    after = monitor.snapshot("flash_attention")
    assert gained("tiles") == 2 * 10 and gained("tiles_skipped") == 2 * 6


# the three call forms the benchmark's other cells trace, lowered here as
# value-and-gradients of the kernels' custom_vjp: sha256 of the jaxpr's
# text, recomputed at PR 40, whose one backward kernel is meant to reach
# all of them (ea9585c's were 552400deb2309687, 1ce92ea17d73c217 and
# 0d43c90354e8ae13). A change to the kernels that is meant to reach those
# cells recomputes them; the block structure is not. Retaken at PR 42,
# which names the vjp-forward's three results: e256fce's texts
# (0bef64586abe9150, 398076cef3b873b6, 8d25934ee86c1597) with three ``name``
# equations more and the later variables' letters moved by them, nothing
# else (compared line by line with the letters taken out).
PARENT_JAXPRS = {
    "seq512": "a2968dc5da9e054d",
    "nemotron": "edb2f8e1d4cb1eae",
    "joyai": "60540556dedf1c9b",
}


def _call_forms():
    S, bf = jax.ShapeDtypeStruct, jnp.bfloat16
    return {
        "seq512": ((S((16, 12, 512, 64), bf),) * 3
                   + (S((16, 1, 1, 512), jnp.float32),), False),
        "nemotron": ((S((1, 32, 8192, 128), bf),) * 3, True),
        "joyai": ((S((1, 32, 8192, 192), bf),) * 2
                  + (S((1, 32, 8192, 128), bf),), True),
    }


@pytest.mark.parametrize("form", sorted(PARENT_JAXPRS))
def test_a_call_without_the_structure_traces_the_parents_kernels(form):
    args, causal = _call_forms()[form]

    def value_and_grads(q, k, v, *mask):
        bq, bk = flash_mod._blocks_that_fit(q.shape[2], q.shape[3],
                                            v.shape[3], 2, 512, 1024)
        mode = flash_mod._mask_mode(mask[0].shape if mask else None,
                                    *q.shape[:3], k.shape[2])
        m = flash_mod._canon_mask(mask[0]) if mask else None

        def loss(q, k, v):
            return jnp.sum(flash_mod._flash(
                q, k, v, m, mode, jnp.zeros((2,), jnp.int32), causal, None,
                bq, bk, 0.0).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = str(jax.make_jaxpr(value_and_grads)(*args))
    assert text.count("pallas_call") == 2
    assert text.count("name[name=flash_") == 3
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENT_JAXPRS[form]


def test_the_structured_call_holds_no_array_of_both_copies_squared():
    """Nothing of 2L x 2L, and no mask operand: every array of the traced
    program has at most one axis of 2 L (or L) rows."""
    import re
    S = jax.ShapeDtypeStruct((1, 2, 2048, 16), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_mod._flash_bd(
            q, k, v, 2, None, 256, 256).astype(jnp.float32)),
        argnums=(0, 1, 2)))(S, S, S))
    assert text.count("pallas_call") == 2
    for shape in re.findall(r"\w+\[([\d,]+)\]", text):
        dims = [int(d) for d in shape.split(",")]
        assert sum(d >= 1024 for d in dims) <= 1, shape
