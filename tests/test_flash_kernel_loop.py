"""The flash kernels' inner loops (ops/pallas/flash_attention.py, PR 32):
loaded tiles reach the MXU in the dtype they were read in; only the score
tiles the diagonal or a true length crosses run the masked body, as
straight-line code behind the plain loop where the shapes say how many
they are; a whole side too large to hold twice is held in one buffer.
Two kernels a call since PR 40: the one backward kernel makes a score tile
once and takes dV, dK and dQ from it, five products a tile, dQ through a
float32 accumulator of the whole q side. Read off the kernels' jaxprs and
counted at the dispatch; the values against float32 attention are in
tests/test_flash_kernel_values.py. CPU only: counts, no time."""
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.ops.pallas import flash_attention
from paddle_tpu.ops.pallas.flash_attention import (_bd_tile_counts,
                                                   _blocks_that_fit,
                                                   _canon_mask,
                                                   _crossed_tiles, _flash,
                                                   _mask_mode,
                                                   _single_buffered,
                                                   _tile_counts)

KERNELS = ("flash_fwd", "flash_bwd")
B, H, BQ, BK, D, DV = 1, 2, 16, 32, 24, 16


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of nested jaxprs included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def _kernel_jaxprs(dtype, causal=True, seq=64, mask_shape=None):
    """name -> jaxpr of each of the two kernels at (1, 2, seq, 24 | 16),
    16 x 32 blocks."""
    mode = _mask_mode(mask_shape, B, H, seq, seq)

    def loss(q, k, v, *mask):
        m = _canon_mask(mask[0]) if mask else None
        return _flash(q, k, v, m, mode, jnp.zeros((2,), jnp.int32), causal,
                      None, BQ, BK, 0.0).astype(jnp.float32).sum()

    qk = jnp.zeros((B, H, seq, D), dtype)
    v = jnp.zeros((B, H, seq, DV), dtype)
    mask = () if mask_shape is None else (jnp.zeros(mask_shape, jnp.float32),)
    outer = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(qk, qk, v,
                                                              *mask)
    found = {e.params["name"]: e.params["jaxpr"]
             for e in _eqns(outer.jaxpr) if e.primitive.name == "pallas_call"}
    assert sorted(found) == sorted(KERNELS)
    return found


def _loops(kernel):
    """The bodies of a kernel's tile loops (``fori_loop`` is a ``while``
    under traced bounds and a ``scan`` under static ones): the loops of the
    kernel's own level. The backward's store of dQ, q-block by q-block, is
    under the ``cond`` of a head's last k-block and not among them."""
    return [e.params["body_jaxpr" if e.primitive.name == "while"
                     else "jaxpr"].jaxpr for e in kernel.eqns
            if e.primitive.name in ("while", "scan")]


def _eqns_of(eqn):
    """Every equation under one equation's nested jaxprs."""
    for v in eqn.params.values():
        for sub in (v if isinstance(v, (list, tuple)) else (v,)):
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                yield from _eqns(sub)


def _names(jaxpr):
    return [e.primitive.name for e in _eqns(jaxpr)]


# -- (a) what the products take ---------------------------------------------

# tile bodies a causal 64 x 64 call at 16 x 32 holds: the plain loop's, and
# the crossed tiles behind it (one a q-block; two q-blocks a k-block)
BODIES = {"flash_fwd": 2, "flash_bwd": 3}
# forward: q k^T, p v; backward: k q^T, v dO^T, p^T dO, ds^T q, k^T ds^T
PRODUCTS = {"flash_fwd": 2, "flash_bwd": 5}
# ... and once a program, outside every tile, the backward makes K^T as the
# product I K^T
ONCE = {"flash_fwd": 0, "flash_bwd": 1}


@pytest.mark.parametrize("name", KERNELS)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_products_take_their_operands_as_they_were_read(dtype, name):
    kernel = _kernel_jaxprs(dtype)[name]
    dots = [e for e in _eqns(kernel) if e.primitive.name == "dot_general"]
    assert len(dots) == BODIES[name] * PRODUCTS[name] + ONCE[name]
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [dtype, dtype], e
        assert e.outvars[0].aval.dtype == jnp.float32
        assert e.params["preferred_element_type"] == jnp.float32
    widened = [e.outvars[0].aval.shape for e in _eqns(kernel)
               if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.dtype == jnp.bfloat16
               and e.outvars[0].aval.dtype == jnp.float32]
    # bfloat16 in: no K or V tile is widened anywhere; q is, to be scaled in
    # float32 and rounded back: once a q-block in the forward, once a tile
    # in the backward kernel, which walks q-blocks
    assert widened == ([] if dtype == jnp.float32 else [(BQ, D)] * (
        BODIES[name] if name == "flash_bwd" else 1)), widened
    (body,) = _loops(kernel)
    # no copy of a tile is transposed: the products contract over the
    # operands' own last dimensions, or take an operand as it stands (K^T,
    # made once a program, against ds^T)
    assert "transpose" not in _names(body)
    for e in _eqns(body):
        if e.primitive.name == "dot_general":
            (lhs, rhs), batch = e.params["dimension_numbers"]
            assert (tuple(lhs), tuple(rhs)) in (((1,), (1,)), ((1,), (0,)))
            assert batch == ((), ())
    # statistics and accumulators are carried in float32
    carried = {v.aval.dtype for v in body.outvars if v.aval.shape}
    assert carried == {jnp.dtype(jnp.float32)}, carried
    # what a kernel makes itself (p, ds, p^T, ds^T) is rounded to the
    # inputs' dtype in front of its product
    narrowed = [e for e in _eqns(body)
                if e.primitive.name == "convert_element_type"
                and e.outvars[0].aval.dtype == jnp.bfloat16
                and e.invars[0].aval.shape in ((BQ, BK), (BK, BQ))]
    assert len(narrowed) == (0 if dtype == jnp.float32 else
                             2 if name == "flash_bwd" else 1)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_dq_is_accumulated_in_float32_and_transposed_once_a_head(dtype):
    """The backward kernel's scratch: dq^T of the whole q side, (D, Sq)
    float32 whatever the call's dtype, and one K^T block in the operands'
    dtype. A tile body adds to one (D, BQ) float32 window of it; the one
    transposition of the kernel is at the store, a q-block at a time,
    under the ``cond`` of the head's last k-block (the first zeroes)."""
    seq = 64
    kernel = _kernel_jaxprs(dtype)["flash_bwd"]
    acc, kt = kernel.invars[-2:]
    assert (acc.aval.shape, acc.aval.dtype) == ((D, seq), jnp.float32)
    assert (kt.aval.shape, kt.aval.dtype) == ((D, BK), dtype)
    (body,) = _loops(kernel)
    writes = [e for e in _eqns(body)
              if e.primitive.name in ("swap", "addupdate")
              and e.invars[0].aval.shape == (D, seq)]
    assert len(writes) == 1
    assert writes[0].invars[1].aval.shape == (D, BQ)
    assert writes[0].invars[1].aval.dtype == jnp.float32
    conds = [e for e in kernel.eqns if e.primitive.name == "cond"]
    assert len(conds) == 2
    transposes = [e for e in _eqns(kernel) if e.primitive.name == "transpose"]
    assert [e.invars[0].aval.shape for e in transposes] == [(D, BQ)]
    assert transposes[0].invars[0].aval.dtype == jnp.float32
    assert "transpose" in [e.primitive.name for e in _eqns_of(conds[1])]


# -- (b) a plain loop, and the crossed tiles behind it -----------------------

_POSITIONS = {"iota", "select_n", "ge", "lt", "and"}


@pytest.mark.parametrize("name", KERNELS)
def test_a_causal_kernel_masks_its_crossed_tiles_outside_the_loop(name):
    """A square causal call of whole blocks: how many tiles the diagonal
    crosses in a program is a fact of the shapes, so they are straight-line
    code and the one loop makes no position."""
    kernel = _kernel_jaxprs(jnp.bfloat16)[name]
    (plain,) = _loops(kernel)
    assert not _POSITIONS & set(_names(plain))
    outside = [e.primitive.name for e in kernel.eqns]
    # two iotas a crossed tile (and the identity of the backward's I K^T)
    assert outside.count("iota") == 2 * (BODIES[name] - 1 + ONCE[name])


@pytest.mark.parametrize("own,other,want", [
    (512, 512, 1), (256, 512, 1), (512, 256, 2), (1024, 256, 4),
    (384, 256, None)])
def test_crossed_tiles_are_counted_from_the_shapes(own, other, want):
    geom = dict(block_q=own, block_k=other, sq=3072, sk=3072)
    assert _crossed_tiles(own, other, causal=True, **geom) == want
    assert _crossed_tiles(own, other, causal=False, **geom) is None
    for odd in (dict(geom, sq=3000), dict(geom, sk=3000)):
        assert _crossed_tiles(own, other, causal=True, **odd) is None


@pytest.mark.parametrize("name", KERNELS)
def test_a_true_length_inside_a_block_keeps_the_masked_loop(name):
    """Causal at 80 = 2.5 k-blocks: the crossed tiles differ from program
    to program, so they are a loop of their own."""
    loops = _loops(_kernel_jaxprs(jnp.bfloat16, seq=80)[name])
    with_positions = ["iota" in _names(body) for body in loops]
    assert with_positions == ([True, False] if name == "flash_bwd"
                              else [False, True])
    plain = loops[with_positions.index(False)]
    assert not _POSITIONS & set(_names(plain))


@pytest.mark.parametrize("name", KERNELS)
def test_aligned_lengths_without_a_diagonal_run_the_plain_loop_alone(name):
    """BERT's seq-512 call: a key bias, no causal mask, whole blocks. The
    bias is one add; no position is made anywhere in the kernel."""
    kernel = _kernel_jaxprs(jnp.bfloat16, causal=False,
                            mask_shape=(B, 1, 1, 64))[name]
    assert len(_loops(kernel)) == 1
    # ... but the (D, D) identity the backward makes K^T with
    iotas = [e.outvars[0].aval.shape for e in _eqns(kernel)
             if e.primitive.name == "iota"]
    assert iotas == [(D, D)] * 2 * ONCE[name]


@pytest.mark.parametrize("name", KERNELS)
def test_a_partial_block_is_masked_and_the_whole_ones_are_not(name):
    """40 = 2.5 q-blocks, no causal mask: the last k-block of every q-block
    and every tile of the last q-block run the masked body."""
    loops = _loops(_kernel_jaxprs(jnp.float32, causal=False, seq=40)[name])
    with_positions = ["iota" in _names(body) for body in loops]
    # backward: the k-blocks' partial one, the plain tiles, the q tail
    assert with_positions == ([True, False, True] if name == "flash_bwd"
                              else [False, True])


# -- (c) the counters at the three flash cells' shapes ------------------------

def _traced_counts(shape_qk, shape_v, dtype, causal, mask_shape=None):
    """What one call site adds to the dispatch's counters (traced, not
    run: the shapes are the cells')."""
    args = [jax.ShapeDtypeStruct(shape_qk, dtype)] * 2 + [
        jax.ShapeDtypeStruct(shape_v, dtype)]
    if mask_shape is not None:
        args.append(jax.ShapeDtypeStruct(mask_shape, jnp.float32))

    def call(q, k, v, *mask):
        return flash_attention(
            pt.Tensor(q), pt.Tensor(k), pt.Tensor(v),
            attn_mask=pt.Tensor(mask[0]) if mask else None, causal=causal,
            force=True).data

    before = monitor.snapshot("flash_attention")
    jax.eval_shape(call, *args)
    after = monitor.snapshot("flash_attention")
    # what this call gained: counters of the process that other tests have
    # touched and this call has not are left out
    gained = {key.split(".", 1)[1]: after[key] - before.get(key, 0)
              for key in after}
    return {key: n for key, n in gained.items() if n}


@pytest.mark.parametrize("cell,qk,v,causal,mask,blocks,tiles,masked", [
    ("joyai_llm_flash.causal_pretrain", (1, 32, 8192, 192),
     (1, 32, 8192, 128), True, None, (512, 512), 4352, 512),
    ("nemotron3_nano_30b_a3b.causal_pretrain", (1, 32, 8192, 128),
     (1, 32, 8192, 128), True, None, (512, 512), 4352, 512),
    ("bert_base.pretrain_seq512", (16, 12, 512, 64), (16, 12, 512, 64),
     False, (16, 1, 1, 512), (512, 1024), 192, 0),
], ids=["joyai", "nemotron", "seq512"])
def test_counters_at_a_cells_shape(cell, qk, v, causal, mask, blocks, tiles,
                                   masked):
    # by hand: 32 heads x sum(i + 1 for i in range(8192 // 512)) = 32 x 136
    # tiles, one diagonal tile a q-block; seq512 one whole tile a program
    assert _blocks_that_fit(qk[2], qk[3], v[3], 2, 512, 1024) == blocks
    # two buffers of the whole side leave no room for such tiles at
    # 8,192 x 192 / 128 alone
    assert _single_buffered(qk[2], qk[3], v[3], 2) == cell.startswith("joyai")
    seen = _traced_counts(qk, v, jnp.bfloat16, causal, mask)
    whole = qk[0] * qk[1] * (qk[2] // blocks[0]) * -(-qk[2] // blocks[1])
    want = {"kernel_traced": 1, "tiles": tiles, "tiles_masked": masked,
            "tiles_skipped": whole - tiles, "native_operands_traced": 1}
    assert seen == {key: n for key, n in want.items() if n}


def test_a_float32_caller_is_not_counted_native():
    seen = _traced_counts((1, 2, 64, 16), (1, 2, 64, 16), jnp.float32, True)
    assert seen.get("native_operands_traced", 0) == 0
    assert seen["kernel_traced"] == 1
    assert (seen["tiles"], seen["tiles_masked"]) == (2, 2)    # one block
    # and its kernels' products stay float32 (test (a), ids f32)


@pytest.mark.parametrize("bq,bk,causal,sq,want", [
    (256, 512, True, 8192, (32 * 272, 32 * 32)),   # one diagonal tile each
    (512, 256, True, 8192, (32 * 272, 32 * 32)),   # two a q-block
    (16, 16, True, 40, (32 * 6, 32 * 5)),          # 2.5 blocks: tail masked
    (16, 16, False, 40, (32 * 9, 32 * 5)),
])
def test_tile_counts_by_hand(bq, bk, causal, sq, want):
    assert _tile_counts(32, block_q=bq, block_k=bk, sq=sq, sk=sq,
                        causal=causal) == want


# -- (d) the backward's counters at the four flash cells' shapes --------------

@pytest.mark.parametrize("qk,v,kw,mask,tiles", [
    ((1, 32, 8192, 192), (1, 32, 8192, 128), dict(causal=True), None, 4352),
    ((1, 32, 8192, 128), (1, 32, 8192, 128), dict(causal=True), None, 4352),
    ((16, 12, 512, 64), (16, 12, 512, 64), {}, (16, 1, 1, 512), 192),
    # 32 heads x (16 x 17 + 16) tiles of 512 x 512 over two copies of 8,192
    ((1, 32, 16384, 128), (1, 32, 16384, 128), dict(diffusion_block=4), None,
     9216),
], ids=["joyai", "nemotron", "seq512", "sdar"])
def test_backward_counters_at_a_cells_shape(qk, v, kw, mask, tiles):
    """A call site whose backward is traced counts one fused backward and
    five products a score tile of its forward's walk; a forward alone
    counts neither."""
    args = [jax.ShapeDtypeStruct(qk, jnp.bfloat16)] * 2 + [
        jax.ShapeDtypeStruct(v, jnp.bfloat16)]
    if mask is not None:
        args.append(jax.ShapeDtypeStruct(mask, jnp.float32))

    def call(q, k, v, *m):
        return flash_attention(
            pt.Tensor(q), pt.Tensor(k), pt.Tensor(v),
            attn_mask=pt.Tensor(m[0]) if m else None, force=True,
            **kw).data.astype(jnp.float32).sum()

    def gained(fn):
        before = monitor.snapshot("flash_attention")
        jax.eval_shape(fn, *args)
        after = monitor.snapshot("flash_attention")
        return {key.split(".", 1)[1]: after[key] - before.get(key, 0)
                for key in after}

    forward = gained(call)
    assert forward["kernel_traced"] == 1 and forward["tiles"] == tiles
    assert not forward.get("backward_fused_traced")
    assert not forward.get("backward_products")
    both = gained(jax.grad(call, argnums=(0, 1, 2)))
    assert both["kernel_traced"] == 1 and both["tiles"] == tiles
    assert both["backward_fused_traced"] == 1
    assert both["backward_products"] == 5 * tiles
    if "diffusion_block" in kw:
        assert tiles == _bd_tile_counts(32, 8192, block_q=512, block_k=512,
                                        shift=2)[0]
