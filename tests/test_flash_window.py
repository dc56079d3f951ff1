"""The flash kernels under a sliding window (``flash_attention(causal=True,
window=W)``, kernels ``flash_win_fwd`` / ``flash_win_bwd``; the
smallthinker cell's six window layers): the window as the written rule,
the kernels in interpret mode against dense masked attention, the tile
counts against a brute-force count and at the cell's shape, the dispatch's
counters, and what a call without a window still traces. Kernels and
counts on the CPU; no model is built here (tests/test_smallthinker.py has
the model)."""
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
from benchmark.reference import smallthinker as R               # noqa: E402

# -- the window: the rule, the kernels, the counts ---------------------------

@pytest.mark.parametrize("length,window", [(24, 8), (24, 1), (9, 24),
                                           (16, 16)])
def test_the_window_is_the_written_rule_on_every_pair(length, window):
    mask = flash_mod.sliding_window_mask(length, window)
    for i in range(length):
        for j in range(length):
            assert mask[i, j] == (j <= i and i - j < window), (i, j)
    np.testing.assert_array_equal(
        mask, R.allowed(jnp.arange(length), jnp.arange(length), window))
    # a row sees itself and the window - 1 positions before it
    assert mask.sum(1).tolist() == [min(i + 1, window)
                                    for i in range(length)]


def _dense(q, k, v, window):
    mask = jnp.asarray(flash_mod.sliding_window_mask(q.shape[2], window))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") \
        / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision="highest")


KERNEL_CASES = [      # (length, window, block_q, block_k)
    (64, 16, 16, 16),      # the window a tile
    (64, 32, 16, 16),      # ... two tiles: the diagonal's tiles unrolled
    (64, 20, 16, 16),      # no multiple of the tile
    (70, 20, 16, 16),      # ... nor is the length
    (64, 5, 16, 16),       # narrower than a tile
    (64, 40, 16, 32),      # block_q < block_k
    (64, 40, 32, 16),      # block_q > block_k
    (96, 33, 16, 16),
    (20, 33, 16, 16),      # the length below the window
    (33, 33, 16, 16),      # ... at it
    (128, 100, 512, 1024),     # the defaults: one tile
]


@pytest.mark.parametrize("length,window,block_q,block_k", KERNEL_CASES)
def test_window_kernels_match_dense_masked_attention(length, window, block_q,
                                                     block_k):
    """Interpret mode, float32: forward and all three gradients, q/k 24
    wide and v 16."""
    key = jax.random.key(length * 7 + window)
    q, k, v, ct = (jax.random.normal(jax.random.fold_in(key, i),
                                     (1, 2, length, d))
                   for i, d in enumerate((24, 24, 16, 16)))

    def kernels(q, k, v):
        return flash_mod._flash_win(q, k, v, window, None, block_q, block_k)

    np.testing.assert_allclose(kernels(q, k, v), _dense(q, k, v, window),
                               atol=2e-6)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * ct), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense(*a, window) * ct),
                    (0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", got, want):
        np.testing.assert_allclose(a, b, atol=5e-6, err_msg=f"d{name}")


@pytest.mark.parametrize("length,window,block_q,block_k", KERNEL_CASES)
def test_tile_counts_under_a_window_are_a_brute_force_count(
        length, window, block_q, block_k):
    bq, bk = flash_mod._clamped_blocks(block_q, block_k, length, length)
    geom = dict(block_q=bq, block_k=bk, sq=length, sk=length, causal=True,
                window=window)
    tiles, masked = flash_mod._tile_counts(3, **geom)
    n_q, n_k = -(-length // bq), -(-length // bk)
    padded = np.zeros((n_q * bq, n_k * bk), bool)
    padded[:length, :length] = flash_mod.sliding_window_mask(length, window)
    by_tile = padded.reshape(n_q, bq, n_k, bk)
    holds, whole = by_tile.any((1, 3)), by_tile.all((1, 3))
    assert tiles == 3 * int(holds.sum())
    assert masked == 3 * int((holds & ~whole).sum())
    # the backward kernel's bounds walk the same tiles, k-block by k-block
    walked = np.zeros((n_q, n_k), bool)
    for j in range(n_k):
        start, _, _, end = flash_mod._q_tile_ranges(np, j, **geom)
        assert flash_mod._q_tile_ranges(
            np, j, **dict(geom, window=None))[3] is None
        walked[int(start):int(end), j] = True
    np.testing.assert_array_equal(walked, holds)


def test_tile_counts_at_the_cells_shape_are_the_issues():
    """28 heads x 16,384 rows at 512 x 512 under a window of 4,096: 252 of
    1,024 tiles a head (36 in the first eight q-blocks, then nine each),
    56 of them masked (32 on the diagonal, 24 on the window's edge); the
    causal call 528."""
    bq, bk = flash_mod._blocks_that_fit(16384, 128, 128, 2, 512, 1024)
    assert (bq, bk) == (512, 512)
    assert flash_mod._single_buffered(16384, 128, 128, 2)
    geom = dict(block_q=512, block_k=512, sq=16384, sk=16384, causal=True)
    assert flash_mod._tile_counts(28, window=4096, **geom) == (28 * 252,
                                                               28 * 56)
    assert flash_mod._tile_counts(28, **geom) == (28 * 528, 28 * 32)
    assert abs(100 * 252 / 1024 - 24.609375) < 1e-9
    # every program masks its diagonal tile outside the loop, windowed too
    assert flash_mod._crossed_tiles(512, 512, window=4096, **geom) == 1
    assert flash_mod._crossed_tiles(512, 512, window=600, **geom) is None
    # allowed pairs a head, row by row: the issue's 58,722,304 of
    # 134,225,920
    seen = np.minimum(np.arange(16384) + 1, 4096)
    assert int(seen.sum()) == 58722304
    assert int((np.arange(16384) + 1).sum()) == 134225920


def test_a_window_narrower_than_a_k_block_cuts_the_k_block_to_its_width():
    """The block rule's clause for the phi4_mini_flash cell's window layer
    (40 heads x 8,192 rows at 64 | 128 under a window of 512): 3 MiB a side
    keeps 512 x 1,024, under which a q-block walks two tiles of 1,024 keys
    for the 1,023 its rows see; the clause cuts the k-block to 512, and the
    walk halves. A window as wide as the k-block or wider, and no window,
    keep the blocks they had."""
    assert flash_mod._blocks_that_fit(8192, 64, 128, 2, 512, 1024) \
        == (512, 1024)
    assert flash_mod._window_blocks(512, 512, 1024) == (512, 512)
    assert flash_mod._window_blocks(300, 512, 1024) == (512, 512)
    assert flash_mod._window_blocks(100, 512, 1024) == (512, 128)
    assert flash_mod._window_blocks(48, 512, 1024) == (512, 128)
    for window, blocks in ((None, (512, 1024)), (4096, (512, 512)),
                           (1024, (512, 1024)), (512, (256, 512))):
        assert flash_mod._window_blocks(window, *blocks) == blocks
    geom = dict(sq=8192, sk=8192, causal=True, window=512)
    wide, _ = flash_mod._tile_counts(40, block_q=512, block_k=1024, **geom)
    cut, masked = flash_mod._tile_counts(40, block_q=512, block_k=512, **geom)
    assert (wide, cut, masked) == (40 * 23, 40 * 31, 40 * 31)
    allowed = 512 * 513 // 2 + 7680 * 512
    assert wide * 512 * 1024 / (40 * allowed) > 2.9      # the pairs walked
    assert 1.9 < cut * 512 * 512 / (40 * allowed) < 2.0
    # the dispatch applies it, and counts the tiles the window needs
    q = pt.to_tensor(np.zeros((1, 1, 256, 16), np.float32))
    before = monitor.snapshot("flash_attention.window")
    flash_attention(q, q, q, causal=True, window=32, force=True,
                    block_q=32, block_k=256)
    after = monitor.snapshot("flash_attention.window")
    gained = {k: v - before.get(k, 0) for k, v in after.items()}
    # the k-block of 256 asked for is cut to a lane tile, 128: eight
    # q-blocks of 32 walk one tile each and the one that starts a k-block
    # two; the window's 7,696 pairs would fill 1.9 tiles of 32 x 128
    assert gained["flash_attention.window_tiles"] == 9
    assert gained["flash_attention.window_tiles_skipped"] == 16 - 9
    assert gained["flash_attention.window_tiles_needed"] == 2


def test_the_dispatch_counts_the_path_and_the_tiles_and_refuses_a_mix():
    q = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(3),
                                                  (1, 2, 64, 16))))
    before = monitor.snapshot("flash_attention")
    got = flash_attention(q, q, q, causal=True, window=24, force=True,
                          block_q=16, block_k=16)
    plain = flash_attention(q, q, q, causal=True, window=24)   # sdpa, dense
    np.testing.assert_allclose(got.numpy(), plain.numpy(), atol=2e-6)
    np.testing.assert_allclose(
        got.numpy(), _dense(q.data, q.data, q.data, 24), atol=2e-6)
    after = monitor.snapshot("flash_attention")

    def gained(name):
        return after.get("flash_attention." + name, 0) \
            - before.get("flash_attention." + name, 0)

    assert gained("kernel_traced") == 1 and gained("xla_traced") == 1
    # two heads, four q-blocks of 16 under a window of 24: 1 + 2 + 3 + 3
    # of 16 tiles a head, each crossed by the diagonal or the window's edge
    assert gained("tiles") == 2 * 9 and gained("tiles_masked") == 2 * 9
    assert gained("tiles_skipped") == 2 * 7
    for kw in (dict(causal=False), dict(attn_mask=q, causal=True),
               dict(diffusion_block=4, causal=False),
               dict(causal=True, window=0)):
        with pytest.raises(ValueError, match="window"):
            flash_attention(q, q, q, **{"window": 24, **kw})
    # a window that holds the whole sequence is a causal call
    text = str(jax.make_jaxpr(lambda a: flash_attention(
        pt.to_tensor(a), pt.to_tensor(a), pt.to_tensor(a), causal=True,
        window=64, force=True).data)(q.data))
    assert "flash_fwd" in text and "flash_win" not in text


# a call without a window traces what it traced before PR 41: sha256 of the
# jaxpr's text of value-and-gradients, the digests
# tests/test_flash_block_diffusion.py holds the other cells' call forms to;
# the 16k causal form is the global layers' call, taken at PR 41's parent;
# retaken at PR 42 with those
# (e256fce's 398076cef3b873b6 and f00871eae9b4c0a4 plus the three ``name``
# equations of the saved results, nothing else)
PARENT_JAXPRS = {
    "nemotron": ((1, 32, 8192, 128), "edb2f8e1d4cb1eae"),
    "global_16k": ((1, 28, 16384, 128), "1ceb340bc4a830ff"),
}


def _value_and_grads_text(shape, window=None):
    S = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    bq, bk = flash_mod._blocks_that_fit(shape[2], shape[3], shape[3], 2,
                                        512, 1024)

    def loss(q, k, v):
        if window is not None:
            out = flash_mod._flash_win(q, k, v, window, None, bq, bk)
        else:
            out = flash_mod._flash(q, k, v, None, None,
                                   jnp.zeros((2,), jnp.int32), True, None,
                                   bq, bk, 0.0)
        return jnp.sum(out.astype(jnp.float32))

    return str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        S, S, S))


@pytest.mark.parametrize("form", sorted(PARENT_JAXPRS))
def test_a_call_without_a_window_traces_the_parents_kernels(form):
    shape, digest = PARENT_JAXPRS[form]
    text = _value_and_grads_text(shape)
    assert text.count("pallas_call") == 2
    assert "name=flash_fwd" in text and "name=flash_bwd" in text
    assert "flash_win" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest


def test_the_windowed_call_is_two_kernels_of_its_own_name_and_no_mask():
    """Nothing of 16,384 x 16,384 and no mask operand: every array of the
    traced program has at most one axis of 16,384 rows."""
    import re
    text = _value_and_grads_text((1, 28, 16384, 128), window=4096)
    assert text.count("pallas_call") == 2
    assert "name=flash_win_fwd" in text and "name=flash_win_bwd" in text
    for shape in re.findall(r"\w+\[([\d,]+)\]", text):
        dims = [int(d) for d in shape.split(",")]
        assert sum(d >= 4096 for d in dims) <= 1, shape
