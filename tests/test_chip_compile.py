"""The kernels and serving steps of the main path, compiled for a TPU
v5e that is described, not attached (the TPU compiler is installed on
CPU-only machines). Nothing runs; what the chip's compiler would refuse
— a misaligned slice, too much VMEM — fails here at no chip time.

The topology is described inside a module-scoped fixture and every
compile happens in the test's own process, so nothing here runs at import
or collection time. Several processes can each describe the topology at
once (``ALLOW_MULTIPLE_LIBTPU_LOAD=1``, as in the driver's tier-1 command),
so nothing binds these cases to one process. They are ONE file all the
same, and so one worker's under ``--dist loadfile``: the chip's compiler
is multi-threaded, and files of such cases that start together take the
cores from each other and from every test beside them (CHANGES.md, PR 46,
has the measurement). A kernel's compile cases go here. ``topo``
skips where no topology can be described, and a skip is a lost count:
under the driver's command every case has to PASS. Code that asks
``jax.default_backend()`` sees the CPU in this process; the
``compiled_kernels`` fixture steers ``pallas.interpret_mode`` to the TPU
answer for the duration of one test.
"""
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas as P


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - any failure to describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    monkeypatch.setattr(P, "interpret_mode", lambda: False)


def _is_shape_dtype(x):
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[0], tuple)
            and all(isinstance(n, int) for n in x[0])
            and not isinstance(x[1], tuple))


def _compiled_text(fn, one_chip, *shapes, names=()):
    """Lower ``fn`` at ``(shape, dtype)`` arguments placed on the
    described chip and compile it; returns the compiled program's text.
    ``names`` are the ``name=`` of the ``pl.pallas_call``s the program
    holds: each has to stand in front of a kernel's ``pallas_call`` in
    the compiled text (the op_name of its custom call), where
    ``monitor.profile.instruction_ledger`` and a device trace find it."""
    args = jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(sd[0], sd[1], sharding=one_chip),
        shapes, is_leaf=_is_shape_dtype)
    text = jax.jit(fn).lower(*args).compile().as_text()
    for name in names:
        assert re.search(r"[/(]%s\)*/pallas_call" % re.escape(name), text), \
            f"no kernel named {name} in the compiled text"
    return text


def _compile(fn, one_chip, *shapes, names=()):
    """``_compiled_text``'s tpu_custom_call count."""
    return _compiled_text(fn, one_chip, *shapes,
                          names=names).count("tpu_custom_call")


def _grad_sum(f, argnums=0):
    return jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(),
                    argnums=argnums)


# -- layer norm at BERT-base's width, 8192 rows (64 x 128 or 16 x 512) -----

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_layer_norm_fwd_bwd(one_chip, compiled_kernels, dtype):
    from paddle_tpu.ops.pallas.layer_norm import _layer_norm2
    f = _grad_sum(lambda x, w, b: _layer_norm2(x, w, b, 1e-12),
                  argnums=(0, 1, 2))
    n = _compile(f, one_chip, ((8192, 768), dtype),
                 ((768,), jnp.float32), ((768,), jnp.float32),
                 names=("layer_norm_fwd", "layer_norm_bwd"))
    assert n == 2       # forward and backward kernels


# -- flash attention at BERT-base head geometry ----------------------------

def _flash_grad(seq, mask_shape=None, causal=False, dropout=0.0, batch=1):
    from paddle_tpu.ops.pallas.flash_attention import (_canon_mask, _flash,
                                                       _mask_mode)
    mode = _mask_mode(mask_shape, batch, 12, seq, seq)
    assert mode != "fallback"

    def f(q, k, v, seed, *mask):
        m = _canon_mask(mask[0]) if mask else None
        return _flash(q, k, v, m, mode, seed, causal, None, 512, 1024,
                      dropout)

    return _grad_sum(f, argnums=(0, 1, 2))


# 640: not a multiple of block_q (a masked tail block); 384: under it
@pytest.mark.parametrize("seq", [512, 2048, 640, 384])
@pytest.mark.parametrize("dropout", [0.0, 0.1], ids=["nodrop", "drop"])
def test_flash_fwd_bwd(one_chip, compiled_kernels, seq, dropout):
    qkv = ((1, 12, seq, 64), jnp.bfloat16)
    n = _compile(_flash_grad(seq, dropout=dropout), one_chip,
                 qkv, qkv, qkv, ((2,), jnp.int32),
                 names=("flash_fwd", "flash_bwd"))
    assert n == 2       # forward, backward


def test_flash_bert_padding_mask(one_chip, compiled_kernels):
    """BERT's additive [B, 1, 1, S] mask tiles as a 'key' mask — it must
    reach the kernel, not the sdpa fallback."""
    qkv = ((1, 12, 512, 64), jnp.bfloat16)
    mask = (1, 1, 1, 512)
    n = _compile(_flash_grad(512, mask_shape=mask, dropout=0.1), one_chip,
                 qkv, qkv, qkv, ((2,), jnp.int32), (mask, jnp.float32))
    assert n == 2


_SHAPE = re.compile(r"\b(f32|bf16)\[([0-9,]*)\]")


def _shapes(text, dtype):
    """Every ``dtype[d0,d1,...]`` in ``text`` as a tuple of ints."""
    return [tuple(int(n) for n in dims.split(",") if n)
            for dt, dims in _SHAPE.findall(text) if dt == dtype]


def test_flash_row_statistics_cross_hbm_one_value_a_row(one_chip,
                                                        compiled_kernels):
    """``bert_base.pretrain_seq512``'s attention, forward + backward: the
    soft-max row statistics (m, l, 1/l, delta) are one f32 a (batch*head,
    row) wherever they touch HBM — no 128-lane replica as a kernel
    result, an operand, or a broadcast between the kernels (a count over
    the compiled text, no time)."""
    b, h, seq, d = 16, 12, 512, 64
    qkv = ((b, h, seq, d), jnp.bfloat16)
    mask = (b, 1, 1, seq)
    names = ("flash_fwd", "flash_bwd")
    text = _compiled_text(_flash_grad(seq, mask_shape=mask, batch=b),
                          one_chip, qkv, qkv, qkv, ((2,), jnp.int32),
                          (mask, jnp.float32), names=names)
    assert text.count("tpu_custom_call") == 2

    rows = b * h * seq
    assert "f32[%d,%d,128]" % (b * h, seq) not in text
    # nowhere in the program, fusions included, an f32 array of 128 (or
    # more) values a row: q/k/v/o upcast to f32 is 64 a row
    wide = [s for s in _shapes(text, "f32") if np.prod(s) >= 128 * rows]
    assert not wide, wide
    for line in text.splitlines():
        if " broadcast(" in line:
            result = line.split(" broadcast(")[0]
            assert not [s for s in _shapes(result, "f32")
                        if len(s) > 1 and s[-1] == 128
                        and np.prod(s) >= rows], line

    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(calls) == 2
    qkvo = {(b * h, seq, d), (b, h, seq, d)}
    stats = 0
    for line in calls:
        head = line.split("backend_config=")[0]     # result and operands
        assert qkvo & set(_shapes(head, "bf16")), head
        for s in _shapes(head, "f32"):
            if s in qkvo or s == (b, 1, 1, seq):    # the key mask
                continue
            assert np.prod(s) <= 8 * rows, (s, head)
            stats += np.prod(s) == rows
    # m and l out of the forward; m, 1/l and delta into the backward
    # kernel, each once among the operands' layouts
    assert stats >= 2 + 3


def test_flash_causal(one_chip, compiled_kernels):
    qkv = ((1, 12, 512, 64), jnp.bfloat16)
    n = _compile(_flash_grad(512, causal=True), one_chip,
                 qkv, qkv, qkv, ((2,), jnp.int32))
    assert n == 2


# 8,192 x 128 causal, 32 heads: the nemotron cell's attention after its K/V
# heads are repeated. The op picks the blocks (``_blocks_that_fit``): a
# kernel keeps the whole other side of a (batch, head) in VMEM, and inside
# the compiled step the parent's dK/dV kernel with BK = 1024 asked for 18.4
# MiB of the 16 a kernel may use unasked. 16,384: the longest sequence the
# whole-side design holds at this head size (at 32,768 the forward's 16 MiB
# of K and V are the default limit alone)
@pytest.mark.parametrize("seq", [16384, 8192, 4096])
def test_flash_long_causal_at_head_size_128(one_chip, compiled_kernels, seq):
    from paddle_tpu.ops.pallas.flash_attention import (_blocks_that_fit,
                                                       _flash)
    block_q, block_k = _blocks_that_fit(seq, 128, 128, 2, 512, 1024)
    assert (block_q, block_k) == (512, 1024 if seq == 4096 else 512)
    # BERT's, as asked
    assert _blocks_that_fit(512, 64, 64, 2, 512, 1024) == (512, 1024)

    def f(q, k, v, seed):
        return _flash(q, k, v, None, None, seed, True, None, block_q,
                      block_k, 0.0)

    qkv = ((1, 32, seq, 128), jnp.bfloat16)
    n = _compile(_grad_sum(f, argnums=(0, 1, 2)), one_chip, qkv, qkv, qkv,
                 ((2,), jnp.int32), names=("flash_fwd", "flash_bwd"))
    assert n == 2


# 8,192 x (192 | 128) causal, 32 heads: the joyai_llm_flash cell's latent
# attention — q and k 128 position-free + 64 rotary wide, v and the output
# 128. 192 lanes are a block's whole last dimension (one and a half lane
# tiles, two in VMEM); v is not padded to 192 anywhere. Two buffers of 6 MiB
# of whole side leave room for 256 x 256 tiles; the kernels hold it in one
# buffer there (PR 32) and the rule's 512 x 512 fit
@pytest.mark.parametrize("seq", [8192, 4096])
def test_flash_long_causal_at_head_sizes_192_and_128(one_chip,
                                                     compiled_kernels, seq):
    from paddle_tpu.ops.pallas.flash_attention import (_blocks_that_fit,
                                                       _flash,
                                                       _single_buffered)
    block_q, block_k = _blocks_that_fit(seq, 192, 128, 2, 512, 1024)
    assert (block_q, block_k) == (512, 512 if seq == 8192 else 1024)
    assert _single_buffered(seq, 192, 128, 2) == (seq == 8192)
    assert not _single_buffered(8192, 128, 128, 2)

    def f(q, k, v, seed):
        return _flash(q, k, v, None, None, seed, True, None, block_q,
                      block_k, 0.0)

    qk = ((1, 32, seq, 192), jnp.bfloat16)
    v = ((1, 32, seq, 128), jnp.bfloat16)
    text = _compiled_text(_grad_sum(f, argnums=(0, 1, 2)), one_chip, qk, qk,
                          v, ((2,), jnp.int32),
                          names=("flash_fwd", "flash_bwd"))
    assert text.count("tpu_custom_call") == 2
    # v, o, dO and dV cross HBM 128 wide, q, k, dq and dk 192 wide: per
    # kernel (192-wide, 128-wide) operands and results — forward q k | v o,
    # backward q k dq dk | v dO dv
    calls = [line.split("backend_config=")[0] for line in text.splitlines()
             if "tpu_custom_call" in line and " custom-call(" in line]
    widths = sorted(
        tuple(_shapes(head, "bf16").count((32, seq, w)) for w in (192, 128))
        for head in calls)
    assert widths == [(2, 2), (4, 3)], widths


def _kernel_eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernel_eqns(sub)


# 2 x 8,192 x 128 under the block-diffusion structure, 32 heads: the
# sdar_30b_a3b_chat cell's attention after its K/V heads are repeated. The
# kernels hold ONE copy's side (4 MiB, the causal 8k call's) beside the
# noisy copy's block at the program's own positions, so the same rule gives
# the same 512 x 512; nothing of 16,384 x 16,384 exists, and the row
# statistics stay one float32 a row
def test_flash_block_diffusion_at_the_cells_size(one_chip, compiled_kernels):
    from paddle_tpu.ops.pallas.flash_attention import (_blocks_that_fit,
                                                       _flash_bd)
    block_q, block_k = _blocks_that_fit(8192, 128, 128, 2, 512, 1024)
    assert (block_q, block_k) == (512, 512)

    def f(q, k, v):
        return _flash_bd(q, k, v, 2, None, block_q, block_k)

    qkv = ((1, 32, 16384, 128), jnp.bfloat16)
    text = _compiled_text(_grad_sum(f, argnums=(0, 1, 2)), one_chip, qkv,
                          qkv, qkv, names=("flash_bd_fwd", "flash_bd_bwd"))
    assert text.count("tpu_custom_call") == 2
    assert not re.search(r"\[(\d+,)*16384,(\d+,)*16384[,\]]", text)
    assert "f32[32,1,16384]" in text


_SCOPED = r'"%s":\[\{"memory_space":"1","offset":"0","size":"(\d+)"'


def _vmem_of_kernels(text):
    """name -> (the VMEM a kernel was allowed, what it took) in bytes, for
    every kernel of a compiled program's text."""
    out = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            name = re.search(r"[/(](\w+)\)*/pallas_call", line).group(1)
            out[name] = tuple(
                int(re.search(_SCOPED % key, line).group(1)) for key in
                ("scoped_memory_configs", "used_scoped_memory_configs"))
    return out


# 16,384 x 128 under a sliding window of 4,096, 28 heads: the
# smallthinker_21b_a3b cell's window layers after their K/V heads are
# repeated. The kernels of the causal call with one more fact: the same
# blocks (512 x 512, the whole side in one buffer), the forward inside the
# 16 MiB a kernel may use unasked, the backward inside the limit its shapes
# give; no mask operand, nothing of 16,384 x 16,384
def test_flash_window_at_the_cells_size(one_chip, compiled_kernels):
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    block_q, block_k = fa._blocks_that_fit(16384, 128, 128, 2, 512, 1024)
    assert (block_q, block_k) == (512, 512)

    def f(q, k, v):
        return fa._flash_win(q, k, v, 4096, None, block_q, block_k)

    qkv = ((1, 28, 16384, 128), jnp.bfloat16)
    text = _compiled_text(_grad_sum(f, argnums=(0, 1, 2)), one_chip, qkv,
                          qkv, qkv, names=("flash_win_fwd", "flash_win_bwd"))
    assert text.count("tpu_custom_call") == 2
    assert not re.search(r"\[(\d+,)*16384,(\d+,)*16384[,\]]", text)
    assert "f32[28,1,16384]" in text
    vmem = _vmem_of_kernels(text)
    mib = 2 ** 20
    allowed, took = vmem["flash_win_fwd"]
    assert took <= allowed == 16 * mib
    allowed, took = vmem["flash_win_bwd"]
    want = fa._bwd_params(16384, 128, 128, 2, 512, 512, True,
                          extra=512).vmem_limit_bytes
    assert want <= allowed < want + mib
    assert 16 * mib < took <= allowed < 48 * mib


# 16,384 x 128 under a learned selection, 32 heads: the keye_vl2_30b_a3b
# cell's attention after its K/V heads are repeated. The causal call's two
# kernel bodies with one more operand, the int8 selection [1, 1, S, S] (a
# q-block's rows forward, a k-block's rows of its transpose backward): the
# same blocks, each call inside the VMEM limit its shapes give, and the
# only S x S array of the program is the int8 one
def test_flash_under_a_selection_at_the_cells_size(one_chip,
                                                    compiled_kernels):
    import importlib
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    block_q, block_k = fa._blocks_that_fit(16384, 128, 128, 2, 512, 1024)

    def f(q, k, v, selected):
        return fa._flash_sel(q, k, v, selected, None, block_q, block_k)[0]

    qkv = ((1, 32, 16384, 128), jnp.bfloat16)
    text = _compiled_text(
        _grad_sum(f, argnums=(0, 1, 2)), one_chip, qkv, qkv, qkv,
        ((1, 1, 16384, 16384), jnp.int8),
        names=("flash_sel_fwd", "flash_sel_bwd"))
    assert text.count("tpu_custom_call") == 2
    assert not re.search(r"(f32|bf16)\[(\d+,)*16384,(\d+,)*16384[,\]]", text)
    assert "s8[1,1,16384,16384]" in text and "f32[32,1,16384]" in text
    vmem = _vmem_of_kernels(text)
    mib = 2 ** 20
    allowed, took = vmem["flash_sel_fwd"]
    assert allowed == fa._sel_fwd_vmem(16384, 128, 128, 2, 512, 512, True)
    assert 16 * mib < took <= allowed < 48 * mib
    allowed, took = vmem["flash_sel_bwd"]
    assert 16 * mib < took <= allowed < 64 * mib


# the indexer's two kernels at the cell's size (16 heads of 64 over 16,384
# rows): the selection holds a block of 128 rows' scores in VMEM and writes
# the selection as packed bits, 256 KiB a program (its VMEM allowance
# follows that block: the held scores, the packed elements as int32 and
# two int8 buffers of them), and nothing S x S; the loss's pass reads the
# selection transposed and leaves four float32 results; neither makes a
# float S x S array
def test_the_indexers_kernels_at_the_cells_size(one_chip, compiled_kernels):
    from paddle_tpu.ops.pallas import dsa
    s = 16384
    qi, ki, w = (((1, 16, s, 64), jnp.bfloat16), ((1, s, 64), jnp.bfloat16),
                 ((1, s, 16), jnp.float32))
    assert dsa.select_supported(qi[0])
    text = _compiled_text(lambda *a: dsa.select(*a, top_k=2048), one_chip,
                          qi, ki, w, names=("dsa_select",))
    assert text.count("tpu_custom_call") == 1
    assert "s8[1,1,16384,2048]" in text
    assert not re.search(r"\[(\d+,)*16384,(\d+,)*16384[,\]]", text)
    allowed, took = _vmem_of_kernels(text)["dsa_select"]
    mib = 2 ** 20
    held, packed = 128 * s * 4, 128 * (s // 8) * (4 + 2 * 1)
    assert allowed == held + packed + 4 * s * 128 * 2 + 16 * mib
    assert held + packed < took <= allowed < 48 * mib
    qk = ((1, 32, s, 128), jnp.bfloat16)
    stat = ((32, 1, s), jnp.float32)
    assert dsa.kl_supported(qk[0], qi[0])
    text = _compiled_text(
        lambda *a: dsa.kl_and_grads(*a, None), one_chip, qk, qk, stat, stat,
        ((1, 1, s, s), jnp.int8), qi, ki, w, ((1, s), jnp.float32),
        names=("dsa_kl",))
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"(f32|bf16)\[(\d+,)*16384,(\d+,)*16384[,\]]", text)
    allowed, took = _vmem_of_kernels(text)["dsa_kl"]
    assert took <= allowed < 96 * 2 ** 20


# the one backward kernel at the four flash cells' shapes, at 16,384 x 128
# and at the lfm2 cell's 2 x 32 x 8,192 x 64 (two buffers a side, 512 x
# 1,024 tiles): beside the whole q side (q, dO, statistics) it holds dq's block and
# dq's float32 accumulator, which the default 16 MiB do not hold with 512 x
# 512 tiles; its limit is computed from the call's shapes
@pytest.mark.parametrize("shape,kw,blocks", [
    ((1, 32, 8192, 192, 128), dict(causal=True), (512, 512)),
    ((1, 32, 8192, 128, 128), dict(causal=True), (512, 512)),
    ((1, 32, 16384, 128, 128), dict(shift=2), (512, 512)),
    ((16, 12, 512, 64, 64), dict(mask=(16, 1, 1, 512)), (512, 1024)),
    ((1, 32, 16384, 128, 128), dict(causal=True), (512, 512)),
    ((2, 32, 8192, 64, 64), dict(causal=True), (512, 1024)),
], ids=["joyai", "nemotron", "sdar", "seq512", "16k", "lfm2"])
def test_flash_backward_fits_the_vmem_limit_its_shapes_give(
        one_chip, compiled_kernels, shape, kw, blocks):
    import importlib
    # (the package's ``flash_attention`` is the op; this is its module)
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    b, h, seq, d, dv = shape
    held = seq // 2 if "shift" in kw else seq
    block_q, block_k = fa._blocks_that_fit(held, d, dv, 2, 512, 1024)
    assert (block_q, block_k) == blocks
    mask = kw.get("mask")
    mode = fa._mask_mode(mask, b, h, seq, seq)

    def f(q, k, v, *m):
        if "shift" in kw:
            return fa._flash_bd(q, k, v, kw["shift"], None, block_q, block_k)
        return fa._flash(q, k, v, fa._canon_mask(m[0]) if m else None, mode,
                         jnp.zeros((2,), jnp.int32), kw.get("causal", False),
                         None, block_q, block_k, 0.0)

    qk, v = (((b, h, seq, w), jnp.bfloat16) for w in (d, dv))
    args = (qk, qk, v) + (((mask, jnp.float32),) if mask else ())
    name = "flash_bd_bwd" if "shift" in kw else "flash_bwd"
    text = _compiled_text(_grad_sum(f, argnums=(0, 1, 2)), one_chip, *args,
                          names=(name,))
    allowed, took = _vmem_of_kernels(text)[name]
    bq, bk = fa._clamped_blocks(block_q, block_k, held, held)
    want = fa._bwd_params(
        held, d, dv, 2, bq, bk, fa._single_buffered(held, d, dv, 2),
        blocks=2 if "shift" in kw else 1,
        extra=0 if "shift" in kw else bk).vmem_limit_bytes
    assert want <= allowed < want + 2 ** 20
    assert took <= allowed
    mib = 2 ** 20
    if held >= 8192:
        # the accumulator and dq's block beside the whole side: more than
        # a kernel may use unasked, a fraction of the 128 MiB there are
        assert 16 * mib < took < allowed < 48 * mib
    else:
        assert took < 4 * mib and allowed == 16 * mib


def test_flash_kernels_widen_no_tile_of_k_or_v(compiled_kernels):
    """What Mosaic is handed at the joyai cell's call (8,192 x 192 | 128,
    512 x 512 tiles): every product takes bfloat16 operands with a float32
    result, and the only tile extended to float32 is q's (512, 192), to be
    scaled and rounded back — once a q-block in the forward kernel, once a
    tile body in the backward kernel (its loop's and the crossed tile's).
    No float32 copy of a K, V or dO tile is made; the MXU would round it
    back."""
    from paddle_tpu.ops.pallas.flash_attention import _flash

    def f(q, k, v, seed):
        return _flash(q, k, v, None, None, seed, True, None, 512, 512, 0.0)

    qk = jax.ShapeDtypeStruct((1, 32, 8192, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 32, 8192, 128), jnp.bfloat16)
    outer = jax.make_jaxpr(_grad_sum(f, argnums=(0, 1, 2)))(
        qk, qk, v, jax.ShapeDtypeStruct((2,), jnp.int32))
    kernels = {e.params["name"]: e.params["jaxpr"]
               for e in _kernel_eqns(outer.jaxpr)
               if e.primitive.name == "pallas_call"}
    assert sorted(kernels) == ["flash_bwd", "flash_fwd"]
    for name, kernel in kernels.items():
        eqns = list(_kernel_eqns(kernel))
        for e in eqns:
            if e.primitive.name == "dot_general":
                assert [x.aval.dtype for x in e.invars] == [jnp.bfloat16] * 2
                assert e.outvars[0].aval.dtype == jnp.float32
        widened = [e.outvars[0].aval.shape for e in eqns
                   if e.primitive.name == "convert_element_type"
                   and e.invars[0].aval.dtype == jnp.bfloat16
                   and e.outvars[0].aval.dtype == jnp.float32]
        assert widened == [(512, 192)] * (2 if name == "flash_bwd"
                                          else 1), (name, widened)


# -- what a recomputed block keeps, in XLA's account of a cell's step --------

def _cell_step_memory(monkeypatch, one_chip, cell, layers, policy,
                      first_layer=None):
    """XLA's memory analysis of a benchmark cell's training step, cut to
    ``layers`` blocks (from source layer ``first_layer`` on, where given),
    compiled for the described chip with the cell's own family file,
    widths and sequence; ``policy`` is what every
    ``jit.recompute`` of the step is given (None: none named). Sizes, no
    time: nothing runs."""
    import importlib
    import json
    import paddle_tpu as pt
    from paddle_tpu import jit
    from benchmark.families import trainer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    config, traffic = cell.split(".")
    with open(os.path.join(root, "benchmark", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           traffic + ".json")) as f:
        traffic = dict(json.load(f), chips=1)
    cfg["num_hidden_layers"] = layers
    if first_layer is not None:
        cfg["first_layer"] = first_layer
    if "sliding_window_layout" in cfg:
        cfg["sliding_window_layout"] = cfg["sliding_window_layout"][:layers]

    class Compiled(Exception):
        pass

    make = jit.StaticFunction._make_entry

    def make_entry(self, *args, **kwargs):
        entry = make(self, *args, **kwargs)
        jitted = entry["jitted"]

        def compile_only(state, arrays):
            shapes = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                               sharding=one_chip),
                (state, arrays))
            raise Compiled(jitted.lower(*shapes).compile().memory_analysis())
        entry["jitted"] = compile_only
        return entry

    monkeypatch.setattr(jit.StaticFunction, "_make_entry", make_entry)
    # the seed's weights are not needed to compile
    monkeypatch.setattr(trainer.Trainer, "load", lambda self, weights: None)
    if policy is not None:
        recompute = jit.recompute
        monkeypatch.setattr(jit, "recompute", lambda *a, **kw: recompute(
            *a, policy=policy, **kw))
    family = importlib.import_module("benchmark.families." + cfg["family"])
    step = family.build(cfg, traffic, None).step
    ids = np.zeros((traffic["batch_per_chip"], traffic["seq_len"]), np.int32)
    with pytest.raises(Compiled) as e:
        step(pt.to_tensor(ids))
    return e.value.args[0]


@pytest.mark.parametrize("cell,layers,attentions,heads,rows,dv", [
    ("smallthinker_21b_a3b.causal_pretrain_16k", 2, 2, 28, 16384, 128),
    # the dense layer and the MTP module: two recomputed blocks
    ("joyai_llm_flash.causal_pretrain", 1, 2, 32, 8192, 128),
], ids=["smallthinker", "joyai"])
def test_kept_flash_results_in_the_steps_temporaries(
        one_chip, compiled_kernels, monkeypatch, cell, layers, attentions,
        heads, rows, dv):
    """Two recomputed blocks of the two cells nearest the chip's 16 GB, at
    their widths and sequence lengths: what the default policy of
    ``jit.recompute`` keeps, a call's o in bfloat16 and two float32 rows,
    is what XLA's temporaries grow by against ``"full"`` (to 10 %; at PR 42
    241.5 MB for 242.2 and 137.9 for 138.4; 0.959 GB for smallthinker's
    eight layers). With expert layers between the attentions XLA's
    scheduler, not the residuals, sets joyai's peak (two layers and the
    module: 33.5 MB more for 207.6 kept; the whole step: 0.43 GB LESS), so
    the whole steps are compiled by hand before a chip is asked
    (PERF.md section 6, PR 42) and this holds the mechanism's bytes."""
    with monkeypatch.context() as patch:
        kept = _cell_step_memory(patch, one_chip, cell, layers, None)
    with monkeypatch.context() as patch:
        full = _cell_step_memory(patch, one_chip, cell, layers, "full")
    results = attentions * (heads * rows * dv * 2 + 2 * heads * rows * 4)
    more = kept.temp_size_in_bytes - full.temp_size_in_bytes
    assert kept.argument_size_in_bytes == full.argument_size_in_bytes
    assert abs(more - results) <= 0.1 * results, (more, results)


def test_the_lfm2_cells_step_holds_its_kernels_and_fits(one_chip,
                                                         compiled_kernels,
                                                         monkeypatch):
    """The first two blocks of ``lfm2_8b_a1b.causal_pretrain_2x8k`` (a conv
    layer over the dense feed-forward, the attention layer over experts)
    at the cell's widths and 2 x 8,192 tokens, through the cell's own
    family file: the step compiles for the chip with both kernel routes
    taken (counts and sizes, no time: nothing runs)."""
    from paddle_tpu import monitor
    before = {p: monitor.snapshot(p)
              for p in ("gated_short_conv", "flash_attention")}
    seen = _cell_step_memory(monkeypatch, one_chip,
                             "lfm2_8b_a1b.causal_pretrain_2x8k", 2, None)

    def gained(prefix, name):
        return monitor.snapshot(prefix).get(f"{prefix}.{name}", 0) \
            - before[prefix].get(f"{prefix}.{name}", 0)

    assert gained("gated_short_conv", "kernel_traced") == 1
    assert gained("gated_short_conv", "xla_traced") == 0
    assert gained("flash_attention", "kernel_traced") == 1
    # 193 M parameters at 12 bytes (weights and two moments; the gradient
    # is a temporary), and temporaries that leave the chip room
    params = 60_827_648 + 98_635_904 + 33_554_432 + 2_048
    assert abs(seen.argument_size_in_bytes - 12 * params) < 2 ** 20
    assert seen.temp_size_in_bytes < 6 * 10 ** 9


# -- the nemotron cell's routed experts: plain XLA, chosen on the device ----

def test_grouped_experts_fwd_bwd_at_the_cells_size(one_chip):
    """8,192 tokens x 2,688 in bfloat16, 8 held experts 1,856 wide, top-6:
    each expert's gather, two products and scatter-add sit in a
    conditional per rung of the ladder (512 ... 8,192 rows), forward and
    hand-written backward; the chip's compiler takes both."""
    from paddle_tpu.ops import moe

    def loss(x, weights, w_up, w_down, experts):
        y, _ = moe._routed(x, experts, weights, w_up, w_down, first=0,
                           dot_dtype=jnp.bfloat16)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    text = _compiled_text(
        jax.grad(loss, argnums=(0, 1, 2, 3)), one_chip,
        ((1, 8192, 2688), jnp.bfloat16), ((1, 8192, 6), jnp.float32),
        ((8, 2688, 1856), jnp.float32), ((8, 1856, 2688), jnp.float32),
        ((1, 8192, 6), jnp.int32))
    assert text.count(" conditional(") == 2          # forward, backward
    for rows in moe._ladder(8192, moe.MIN_ROWS):
        assert f"bf16[{rows},2688]" in text          # a rung's gathered rows
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("tokens,d,f,held,k,gated", [
    (8192, 2688, 1856, 8, 6, False), (16384, 2048, 768, 16, 8, True)],
    ids=["nemotron", "sdar"])
def test_grouped_experts_combine_in_place_at_the_cells_sizes(
        one_chip, compiled_kernels, tokens, d, f, held, k, gated):
    """The same with the scatter-add kernel (PR 38): one call a rung in
    each conditional, the float32 accumulator ``[tokens, 1, d]`` laid out
    row by row, and no branch that copies it whole."""
    from paddle_tpu.ops import moe

    def loss(x, weights, experts, *ws):
        y, _ = moe._routed(x, experts, weights, *ws, first=0,
                           dot_dtype=jnp.bfloat16, kernel=True)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    n = 2 + gated
    text = _compiled_text(
        jax.grad(loss, argnums=(0, 1) + tuple(range(3, 3 + n))), one_chip,
        ((1, tokens, d), jnp.bfloat16), ((1, tokens, k), jnp.float32),
        ((1, tokens, k), jnp.int32), ((held, d, f), jnp.float32),
        ((held, f, d), jnp.float32), *[((held, d, f), jnp.float32)] * gated,
        names=("moe_scatter_add",))
    rungs = len(moe._ladder(tokens, moe.MIN_ROWS))
    assert text.count(" conditional(") == 2
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * rungs
    assert f"f32[{tokens},1,{d}]{{2,1,0:T(1,128)}}" in text
    whole = re.findall(r"= f32\[%d,(?:1,)?%d\]\S* (?:copy|fusion)\("
                       % (tokens, d), text)
    assert len(whole) <= 4, whole     # zeros and the way out, not a branch


# -- the grouped path (PR 44): one product a matrix over sorted rows ---------

GROUPED_CELLS = {   # tokens, d, f, held, k, gated, relu gate
    "lfm2": (16384, 2048, 1792, 8, 4, True, False),
    "smallthinker": (16384, 2560, 768, 8, 6, True, True),
    "sdar": (16384, 2048, 768, 16, 8, True, False),
    "joyai": (8192, 2048, 768, 16, 8, True, False),
}


@pytest.mark.parametrize("cell", sorted(GROUPED_CELLS))
def test_grouped_experts_fwd_bwd_at_the_cells_sizes(one_chip,
                                                    compiled_kernels, cell):
    """``F.moe_experts``' grouped path, forward and hand-written backward,
    at the four cells it runs in: the five kernels of
    ``ops/pallas/moe_grouped.py`` and the scatter-add, each within the VMEM
    its shapes ask for; no conditional an expert (one loop over rounds of
    rows forward, one backward); nothing at the layout's static bound but
    its int32 / float32 tables - what is held of the rows is one round."""
    from paddle_tpu.ops import moe
    tokens, d, f, held, k, gated, relu_gate = GROUPED_CELLS[cell]

    def loss(x, weights, experts, *ws):
        y, _ = moe._routed_tiles(x, experts, weights, *ws, first=0,
                                 dot_dtype=jnp.bfloat16, relu_gate=relu_gate)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    text = _compiled_text(
        jax.grad(loss, argnums=(0, 1) + tuple(range(3, 5 + gated))),
        one_chip, ((1, tokens, d), jnp.bfloat16),
        ((1, tokens, k), jnp.float32), ((1, tokens, k), jnp.int32),
        ((held, d, f), jnp.float32), ((held, f, d), jnp.float32),
        *[((held, d, f), jnp.float32)] * gated,
        names=("moe_hidden", "moe_gmm", "moe_hidden_bwd", "moe_tgmm",
               "moe_scatter_add"))
    # forward: hidden, gmm, scatter-add; backward: hidden_bwd, tgmm x 3,
    # gmm, scatter-add
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    assert " conditional(" not in text
    assert len(re.findall(r" while\(", text)) == 2
    # XLA may keep arrays of its own in VMEM under a kernel's share: the
    # share starts at an offset, and what was used counts from 0
    share = (r'"scoped_memory_configs":\[\{"memory_space":"1",'
             r'"offset":"(\d+)","size":"(\d+)"')
    mib, seen = 2 ** 20, set()
    for line in text.splitlines():
        if "tpu_custom_call" in line and " custom-call(" in line:
            name = re.search(r"[/(](\w+)\)*/pallas_call", line).group(1)
            offset, allowed = map(int, re.search(share, line).groups())
            took = int(re.search(_SCOPED % "used_scoped_memory_configs",
                                 line).group(1)) - offset
            # (hidden_bwd holds three float32 weight blocks twice, their
            # casts and two row tiles: 68-80 MiB of the chip's 128)
            assert 0 < took <= allowed <= 96 * mib, (name, allowed, took)
            seen.add(name)
    assert len(seen) == 5
    bound = tokens * min(k, held)
    assert moe.CHUNK_ROWS < bound
    assert re.search(r"bf16\[%d,%d\]" % (moe.CHUNK_ROWS, d), text)
    assert not re.search(r"(bf16|f32)\[(\d+,)?%d,%d\]" % (
        -(-(bound + held * (moe.ROW_TILE - 1)) // moe.CHUNK_ROWS)
        * moe.CHUNK_ROWS, d), text)


def test_the_nemotron_cells_width_keeps_the_ladder():
    """1,856 is no whole 128-lane tile (14.5): padding the matrices and
    their gradients costs that cell more than its rungs' padding does
    (PERF.md section 6, PR 44), and 384 rows an expert wait for their
    weights either way."""
    assert not P.moe_grouped_mod.supported(2688, 1856, 256)
    assert all(P.moe_grouped_mod.supported(d, f, 256)
               for _, d, f, *_ in GROUPED_CELLS.values())


# -- the nemotron cell's Mamba-2 scan: a kernel pair ------------------------

def test_ssd_scan_kernels_keep_chunk_sized_arrays_in_vmem(one_chip,
                                                          compiled_kernels):
    """``nemotron3_nano_30b_a3b.causal_pretrain``'s scan, forward + backward
    (1 x 8,192 positions, 64 heads x 64 in 8 groups, state 128, chunk 128,
    bfloat16): both kernels fit the chip's VMEM, and the compiled program
    around them holds no decay matrix, no chunk states and no window
    reduction (a count over the compiled text, no time)."""
    from paddle_tpu.ops.pallas import ssd_scan as K
    b, s, h, p, g, n, chunk = 1, 8192, 64, 64, 8, 128, 128
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert K.supported((b, s, h, p), (b, s, g, n), chunk)

    def scan(*a):
        return K.ssd_scan(*a, chunk=chunk, dot_dtype=bf16)

    text = _compiled_text(
        _grad_sum(scan, argnums=tuple(range(7))), one_chip,
        ((b, s, h, p), bf16), ((b, s, h), bf16), ((h,), f32),
        ((b, s, g, n), bf16), ((b, s, g, n), bf16), ((h,), f32), ((h,), f32),
        names=("ssd_fwd", "ssd_bwd"))
    assert text.count("tpu_custom_call") == 2
    assert "reduce-window" not in text
    # chunk x chunk: only the triangle of ones of the in-chunk sums, once,
    # never an array of them (a decay matrix a head and chunk, its mask)
    for dtype in ("f32", "bf16"):
        for shape in _shapes(text, dtype):
            assert shape[-2:] != (chunk, chunk) or len(shape) == 2, shape
    # heads x P x N: only the one residual, [B, K, N, H * P] float32, as
    # the forward kernel's result and the backward kernel's operand
    state = b * (s // chunk) * h * p * n
    big = {shape for shape in _shapes(text, "f32") if np.prod(shape) >= state}
    assert big == {(b, s // chunk, n, h * p)}, big
    # x, y and their gradients cross HBM in the mixer's layout and dtype:
    # no float32 copy of them anywhere (the residual happens to be as large)
    assert not [shape for shape in _shapes(text, "f32")
                if np.prod(shape) == b * s * h * p and shape not in big]


# what ``supported`` says yes to, the chip's compiler has to take: a chunk
# of 256, heads 128 and 256 wide (one head a lane tile), a state of 256
@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 2048, 64, 64, 8, 128, 256),
    (1, 1024, 8, 128, 2, 256, 128),
    (1, 1024, 4, 256, 1, 128, 128),
], ids=["chunk256", "head128-state256", "head256"])
def test_ssd_scan_kernels_compile_where_supported(one_chip, compiled_kernels,
                                                  b, s, h, p, g, n, chunk):
    from paddle_tpu.ops.pallas import ssd_scan as K
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert K.supported((b, s, h, p), (b, s, g, n), chunk)
    count = _compile(
        _grad_sum(lambda *a: K.ssd_scan(*a, chunk=chunk, dot_dtype=bf16),
                  argnums=tuple(range(7))), one_chip,
        ((b, s, h, p), bf16), ((b, s, h), bf16), ((h,), f32),
        ((b, s, g, n), bf16), ((b, s, g, n), bf16), ((h,), f32), ((h,), f32),
        names=("ssd_fwd", "ssd_bwd"))
    assert count == 2


# -- the nemotron cell's mixer: its convolution and gated norm as kernels ----

def test_mixer_stages_cross_hbm_in_bfloat16_and_the_mixers_layout(
        one_chip, compiled_kernels):
    """One ``Mamba2Mixer`` of ``nemotron3_nano_30b_a3b.causal_pretrain``
    (hidden 2,688; 64 heads x 64 in 8 groups, state 128, 4 taps), forward
    + backward at 1 x 8,192 under bfloat16 autocast: the convolution and
    the gated norm are the kernel pairs, and the compiled text holds no
    float32 array of rows x channels of either stage and no group count
    on the sublanes (a count over the compiled text, no time)."""
    import paddle_tpu as pt
    from paddle_tpu import amp, nn
    bf16, f32 = jnp.bfloat16, jnp.float32
    mixer = nn.Mamba2Mixer(2688, 64, 64, 128, n_groups=8)
    params = dict(mixer.named_parameters())
    held = {n: p.data for n, p in params.items()}

    def loss(u, values):
        for n, p in params.items():
            p.data = values[n]
        with pt.no_grad(), amp.auto_cast(dtype="bfloat16"):
            return jnp.sum(mixer(pt.Tensor(u)).data.astype(f32))

    try:
        text = _compiled_text(
            jax.grad(loss, argnums=(0, 1)), one_chip, ((1, 8192, 2688), bf16),
            {n: (tuple(v.shape), v.dtype) for n, v in held.items()},
            names=("conv1d_fwd", "conv1d_bwd", "gated_norm_fwd",
                   "gated_norm_bwd", "ssd_fwd", "ssd_bwd"))
    finally:
        for n, p in params.items():
            p.data = held[n]
    assert text.count("tpu_custom_call") == 6
    # the entry computation's instructions are what reaches HBM (a fused
    # computation's body upcasts in registers)
    entry = text[text.index("\nENTRY "):]
    wide = {shape for shape in _shapes(entry, "f32")
            if shape[-2:] in ((8192, 6144), (8192, 4096))
            or shape == (1024, 8, 8, 512)}
    assert not wide, wide
    # what the mixer holds between its stages is bfloat16, rows x channels
    assert (1, 8192, 6144) in _shapes(entry, "bf16")
    assert (1, 8192, 4096) in _shapes(entry, "bf16")


@pytest.mark.parametrize("b,s,c,taps", [(2, 1024, 768, 4), (1, 384, 128, 2),
                                        (1, 256, 256, 9)],
                         ids=["2x1024x768-K4", "384x128-K2", "256x256-K9"])
def test_conv1d_kernels_compile_where_supported(one_chip, compiled_kernels,
                                                b, s, c, taps):
    from paddle_tpu.ops.pallas import causal_conv1d as K
    assert K.supported((b, s, c), taps)
    for dtype in (jnp.bfloat16, jnp.float32):
        count = _compile(
            _grad_sum(lambda *a: K.causal_conv1d(*a, activation="silu"),
                      argnums=(0, 1, 2)), one_chip,
            ((b, s, c), dtype), ((c, taps), jnp.float32),
            ((c,), jnp.float32), names=("conv1d_bwd",))
        assert count == 1            # the forward's result is not needed


# the lfm2_8b_a1b cell's gated short convolution, 2 x 8,192 x 3 x 2,048 in
# bfloat16 with 3 taps, and two small shapes: the forward reads the three
# thirds of bcx in place and the backward writes d(bcx) whole, so the
# compiled program around the two kernels holds no slice of a third, no
# concatenation, and nothing of rows x channels in float32
@pytest.mark.parametrize("b,s,c,taps", [(2, 8192, 2048, 3), (1, 384, 128, 3),
                                        (2, 256, 384, 4)],
                         ids=["2x8192x2048-K3", "384x128-K3", "2x256x384-K4"])
def test_gated_conv_kernels_compile_where_supported(one_chip,
                                                    compiled_kernels, b, s, c,
                                                    taps):
    from paddle_tpu.ops.pallas import causal_conv1d as K
    assert K.gated_supported((b, s, 3 * c), taps)

    def both(bcx, w, dy):
        y, vjp = jax.vjp(K.gated_short_conv, bcx, w)
        return y, vjp(dy)

    text = _compiled_text(both, one_chip, ((b, s, 3 * c), jnp.bfloat16),
                          ((c, taps), jnp.float32), ((b, s, c), jnp.bfloat16),
                          names=("gated_conv_fwd", "gated_conv_bwd"))
    assert text.count("tpu_custom_call") == 2
    entry = text[text.index("\nENTRY "):]
    assert not [shape for shape in _shapes(entry, "f32")
                if shape[-2:] in ((s, c), (s, 3 * c))]
    assert " concatenate(" not in entry and " slice(" not in entry
    assert (b, s, 3 * c) in _shapes(entry, "bf16")
    # inside what a kernel may use unasked (no limit of its own is given)
    took = [int(re.search(_SCOPED % "used_scoped_memory_configs",
                          line).group(1))
            for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(took) == 2 and 0 < max(took) <= 16 * 2 ** 20


# the two long grouped-query cells' projections on their way to the flash
# kernels (PR 45): 1 x 16,384 rows x 32 / 28 heads of 128 in bfloat16, sdar
# with head norm and rotation at given positions, smallthinker with the
# rotation alone, and a head of two lane tiles. The compiled program holds
# the two kernels and, outside them, nothing of the array's size: no
# transpose, no float32 copy, no half-width slice, concatenation or pad
@pytest.mark.parametrize("s,heads,d,normed,positioned", [
    (16384, 32, 128, True, True), (16384, 28, 128, False, False),
    (512, 2, 256, True, False)], ids=["sdar-q", "smallthinker-q", "d256"])
def test_qk_heads_kernels_compile_where_supported(one_chip, compiled_kernels,
                                                  s, heads, d, normed,
                                                  positioned):
    from paddle_tpu.ops import nn_ops
    from paddle_tpu.ops.pallas import qk_heads as K
    assert K.supported((1, s, heads * d), heads, (s,) if positioned else None)
    attrs = dict(heads=heads, epsilon=1e-6, normed=normed,
                 positioned=positioned, freq=tuple(
                     nn_ops._rotary_frequencies(d, 1e6, "test").tolist()))

    def both(g, x, *rest):
        y, vjp = jax.vjp(lambda *a: K.qk_heads(*a, **attrs), x, *rest)
        return y, vjp(g)[:1 + normed]

    text = _compiled_text(
        both, one_chip, ((1, heads, s, d), jnp.bfloat16),
        ((1, s, heads * d), jnp.bfloat16),
        *([((d,), jnp.float32)] * normed + [((s,), jnp.int32)] * positioned),
        names=("qk_heads_fwd", "qk_heads_bwd"))
    assert text.count("tpu_custom_call") == 2
    entry = text[text.index("\nENTRY "):]
    assert not [shape for shape in _shapes(entry, "f32")
                if shape[-2:] in ((s, heads * d), (heads, d))
                or shape[-3:] == (heads, s, d)]
    assert " transpose(" not in entry and " pad(" not in entry
    assert not [shape for shape in _shapes(entry, "bf16")
                if shape[-1] == d // 2]
    took = [int(re.search(_SCOPED % "used_scoped_memory_configs",
                          line).group(1))
            for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]
    assert len(took) == 2 and 0 < max(took) <= 16 * 2 ** 20


# a latent attention's projections to the flash kernels' heads
# (ops/pallas/mla_heads.py) at the joyai cell's size, and in float32 at two
# lane tiles a width (the gathers' products at the highest precision): the
# two kernels and, outside them, nothing of an array's size but the bitcasts
@pytest.mark.parametrize("s,heads,nope,v,dtype", [
    (8192, 32, 128, 128, jnp.bfloat16), (512, 2, 256, 256, jnp.float32)],
    ids=["joyai", "f32-256"])
def test_mla_heads_kernels_compile_where_supported(one_chip, compiled_kernels,
                                                   s, heads, nope, v, dtype):
    from paddle_tpu.ops import nn_ops
    from paddle_tpu.ops.pallas import mla_heads as K
    ins = [(1, s, heads * (nope + 64)), (1, s, heads * (nope + v)),
           (1, s, 64)]
    outs = [(1, heads, s, nope + 64), (1, heads, s, nope + 64),
            (1, heads, s, v)]
    assert K.supported(*ins, heads, nope, v, [dtype] * 3)
    attrs = dict(heads=heads, nope=nope, v=v, freq=tuple(
        nn_ops._rotary_frequencies(64, 1e4, "test").tolist()))

    def both(gs, *xs):
        y, vjp = jax.vjp(lambda *a: K.mla_heads(*a, **attrs), *xs)
        return y, vjp(tuple(gs))

    text = _compiled_text(
        both, one_chip, tuple((shape, dtype) for shape in outs),
        *((shape, dtype) for shape in ins),
        names=("mla_heads_fwd", "mla_heads_bwd"))
    assert text.count("tpu_custom_call") == 2
    entry = text[text.index("\nENTRY "):]
    assert " transpose(" not in entry and " concatenate(" not in entry
    name = "bf16" if dtype == jnp.bfloat16 else "f32"
    # fusions that write an array of a result's size would be the chain's
    assert not [line for line in entry.splitlines() if " fusion(" in line
                and any(shape[-2:] == (s, d) for d in (nope + 64, v)
                        for shape in _shapes(line.split(" fusion(")[0],
                                             name))]
    took = [int(re.search(_SCOPED % "used_scoped_memory_configs",
                          line).group(1))
            for line in text.splitlines()
            if "tpu_custom_call" in line and " custom-call(" in line]
    limit = K._block_bytes(K._rows(s, nope, v, jnp.dtype(dtype).itemsize),
                           nope, v, jnp.dtype(dtype).itemsize) + 8 * 2 ** 20
    assert len(took) == 2 and 0 < max(took) <= limit


@pytest.mark.parametrize("b,s,d,groups", [(2, 1024, 1024, 8), (1, 384, 2048, 1),
                                          (1, 128, 256, 1)],
                         ids=["2x1024x1024-G8", "384x2048-G1", "128x256-G1"])
def test_gated_norm_kernels_compile_where_supported(one_chip,
                                                    compiled_kernels, b, s, d,
                                                    groups):
    from paddle_tpu.ops.pallas import gated_rms_norm as K
    assert K.supported((b, s, d), groups)
    for dtype in (jnp.bfloat16, jnp.float32):
        count = _compile(
            _grad_sum(lambda *a: K.gated_rms_norm(*a, epsilon=1e-5,
                                                  num_groups=groups),
                      argnums=(0, 1, 2)), one_chip,
            ((b, s, d), dtype), ((b, s, d), dtype), ((d,), jnp.float32),
            names=("gated_norm_bwd",))
        assert count == 1


# -- the two kernels that ship off -----------------------------------------

def test_batch_norm_fwd_bwd(one_chip, compiled_kernels):
    """ResNet-50 stage-1 NHWC activations, flattened channels-last."""
    from paddle_tpu.ops.pallas.batch_norm import _batch_norm2
    f = _grad_sum(lambda x, w, b: _batch_norm2(x, w, b, 1e-5)[0])
    n = _compile(f, one_chip, ((128 * 112 * 112, 64), jnp.bfloat16),
                 ((64,), jnp.float32), ((64,), jnp.float32),
                 names=("batch_norm_stats", "batch_norm_bwd_reduce",
                        "batch_norm_bwd_dx"))
    assert n >= 2
    # the gradient of a sum needs no normalised output: the forward alone
    shapes = (((128 * 112 * 112, 64), jnp.bfloat16), ((64,), jnp.float32),
              ((64,), jnp.float32))
    assert _compile(lambda x, w, b: _batch_norm2(x, w, b, 1e-5)[0], one_chip,
                    *shapes, names=("batch_norm_stats",
                                    "batch_norm_apply")) == 2


def test_softmax_xent_fwd_bwd(one_chip, compiled_kernels):
    """batch 64 x seq 128 rows over BERT's vocabulary."""
    from paddle_tpu.ops.pallas.softmax_xent import _softmax_xent2
    f = _grad_sum(_softmax_xent2)
    n = _compile(f, one_chip, ((8192, 30522), jnp.float32),
                 ((8192, 1), jnp.int32),
                 names=("softmax_xent_fwd", "softmax_xent_bwd"))
    assert n >= 2


# -- the decode server's two steps at the widest demo model ----------------

@pytest.fixture(scope="module")
def engine():
    from paddle_tpu import serving
    model = serving.demo_model(dim=256, heads=4, layers=2, max_len=512,
                               seed=1)
    eng = serving.GenerateEngine(model, slots=8, page=64, factor=2.0,
                                 max_len=512, start=False)
    yield eng
    eng.close()


def _shapes_of(tree):
    return jax.tree_util.tree_map(lambda a: (tuple(a.shape), a.dtype), tree)


def test_engine_decode_step(one_chip, engine):
    cap = engine.pool.seq_buckets[-1]
    s = engine.slots
    arena = {name: ((s, cap) + tail, dt)
             for name, tail, dt in engine.pool._leaf_list}
    vec = lambda dt: ((s,), dt)     # noqa: E731
    _compile(engine._get_decode(cap), one_chip,
             _shapes_of(engine.model.state), arena, vec(jnp.int32),
             vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32),
             vec(jnp.int32), vec(jnp.float32), vec(jnp.uint32),
             vec(jnp.int32))


def test_engine_prefill_holds_flash(one_chip, engine, compiled_kernels):
    """The 512-token prompt bucket sits at flash's crossover: its
    prefill is the causal kernel."""
    bucket = engine.prompt_buckets[-1]
    assert bucket == 512
    one = lambda dt: ((1,), dt)     # noqa: E731
    n = _compile(engine._get_prefill(bucket), one_chip,
                 _shapes_of(engine.model.state), ((1, bucket), jnp.int32),
                 one(jnp.int32), one(jnp.float32), one(jnp.int32),
                 one(jnp.float32), one(jnp.uint32), one(jnp.int32))
    assert n == engine.model.layers


# -- GSPMD cannot partition a Mosaic kernel --------------------------------

def test_auto_kernels_are_off_inside_a_gspmd_trace(compiled_kernels):
    assert P.enabled("layer_norm")
    with pytest.warns(UserWarning, match="cannot partition a Mosaic"):
        with P.gspmd_trace(4):
            assert not P.enabled("layer_norm")
            assert not P.enabled("flash_attention", seq_len=2048)
            P.configure(layer_norm=True)        # forcing still forces
            try:
                assert P.enabled("layer_norm")
            finally:
                P.configure(layer_norm=None)
    assert P.enabled("layer_norm")


def test_to_static_step_on_a_mesh_traces_without_kernels(compiled_kernels):
    """A step whose state spans several devices (the Fleet path) must
    not hold a kernel: with the TPU answer steered in, a traced
    pallas_call would fail to lower on this CPU mesh — the step runs
    because jit.to_static saw the span and traced the XLA path."""
    import paddle_tpu as pt
    from paddle_tpu import jit, nn
    from paddle_tpu.parallel.fleet import DistributedStrategy, Fleet
    pt.seed(0)
    model = nn.Sequential(nn.Linear(16, 32), nn.LayerNorm(32),
                          nn.Linear(32, 4))
    fleet = Fleet()
    st = DistributedStrategy()
    st.mesh_shape = {"dp": 2, "tp": 2}
    fleet.init(strategy=st, devices=jax.devices()[:4])
    model = fleet.distributed_model(model)
    x = fleet.shard_batch(pt.to_tensor(jnp.ones((8, 16), jnp.float32)))
    with pytest.warns(UserWarning, match="spans 4 devices"):
        out = jit.to_static(lambda t: model(t), models=[model],
                            optimizers=[])(x)
    assert out.shape == [8, 4] or tuple(out.shape) == (8, 4)


# -- every kernel has a name of its own -------------------------------------

KERNEL_NAMES = {
    "batch_norm.py": ["batch_norm_stats", "batch_norm_apply",
                      "batch_norm_bwd_reduce", "batch_norm_bwd_dx"],
    "causal_conv1d.py": ["conv1d_fwd", "conv1d_bwd", "gated_conv_fwd",
                         "gated_conv_bwd"],
    "dsa.py": ["dsa_select", "dsa_kl"],
    "flash_attention.py": ["flash_sel_fwd", "flash_fwd", "flash_win_fwd",
                           "flash_sel_bwd", "flash_bwd", "flash_win_bwd",
                           "flash_bd_fwd", "flash_bd_bwd"],
    "gated_rms_norm.py": ["gated_norm_fwd", "gated_norm_bwd"],
    "layer_norm.py": ["layer_norm_fwd", "layer_norm_bwd"],
    "mla_heads.py": ["mla_heads_fwd", "mla_heads_bwd"],
    "moe_grouped.py": ["moe_hidden", "moe_gmm", "moe_hidden_bwd",
                       "moe_tgmm"],
    "moe_scatter_add.py": ["moe_scatter_add"],
    "qk_heads.py": ["qk_heads_fwd", "qk_heads_bwd"],
    "selective_scan.py": ["selective_scan_fwd", "selective_scan_bwd"],
    "softmax_xent.py": ["softmax_xent_fwd", "softmax_xent_bwd"],
    "ssd_scan.py": ["ssd_fwd", "ssd_bwd"],
}


def _pallas_call_names(path):
    """The ``name=`` of every ``pl.pallas_call(...)`` in a source file, in
    order; None where a call site has none or computes it."""
    import ast
    with open(path) as f:
        tree = ast.parse(f.read())
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr == "pallas_call":
            kw = {k.arg: k.value for k in node.keywords}
            name = kw.get("name")
            out.append((node.lineno, name.value
                        if isinstance(name, ast.Constant) else None))
    return [n for _, n in sorted(out)]


@pytest.mark.parametrize("filename", sorted(KERNEL_NAMES))
def test_every_pallas_call_has_a_stable_name_of_its_own(filename):
    """A trace tells kernels apart by these names (PERF.md section 3), so
    a call site without one, or two with the same, is a blind spot."""
    here = os.path.dirname(os.path.abspath(P.__file__))
    assert _pallas_call_names(os.path.join(here, filename)) == \
        KERNEL_NAMES[filename]


def test_no_pallas_call_site_is_left_out_and_no_name_is_used_twice():
    here = os.path.dirname(os.path.abspath(P.__file__))
    found = {f: _pallas_call_names(os.path.join(here, f))
             for f in sorted(os.listdir(here)) if f.endswith(".py")}
    found = {f: names for f, names in found.items() if names}
    assert found == KERNEL_NAMES
    every = [n for names in found.values() for n in names]
    assert len(every) == len(set(every)) == 37


# a registered name switches the kernels of the file of its name; where two
# pairs share a file, those whose names start with its prefix
KERNEL_SWITCHES = {"causal_conv1d": ("causal_conv1d.py", "conv1d_"),
                   "gated_short_conv": ("causal_conv1d.py", "gated_conv_"),
                   "dsa_select": ("dsa.py", "dsa_select"),
                   "dsa_kl": ("dsa.py", "dsa_kl")}


def test_every_registered_kernel_has_a_module_with_a_call_site():
    """``_KERNELS`` against the files the test above has just walked: a
    registered name switches at least one named ``pl.pallas_call`` and
    every call site is under exactly one name, so a name cannot outlive
    its kernel, nor a kernel ship without its switch."""
    switched = {}
    for name in P._KERNELS:
        filename, prefix = KERNEL_SWITCHES.get(name, (name + ".py", ""))
        switched[name] = [k for k in KERNEL_NAMES.get(filename, [])
                          if k.startswith(prefix)]
    assert all(switched.values()), switched
    assert sorted(k for names in switched.values() for k in names) \
        == sorted(k for names in KERNEL_NAMES.values() for k in names)
    assert set(P._KERNELS) == set(P._AUTO_ON)


# -- the phi4_mini_flash cell: Mamba-1's scan pair, flash at 64 | 128 -------

def test_selective_scan_fwd_bwd_at_the_cells_size(one_chip,
                                                  compiled_kernels):
    """1 x 8,192 positions x 5,120 channels, state 16: the forward kernel
    and the backward kernel (the chunk's states made again in VMEM, the
    state's gradient in registers), with every gradient asked for."""
    from paddle_tpu.ops.pallas import selective_scan as K
    s, d, n = 8192, 5120, 16
    f = jax.grad(lambda *a: jnp.sum(K.selective_scan(*a)),
                 argnums=range(6))
    count = _compile(
        f, one_chip, ((1, s, d), jnp.bfloat16), ((1, s, d), jnp.float32),
        ((d, n), jnp.float32), ((1, s, n), jnp.bfloat16),
        ((1, s, n), jnp.bfloat16), ((d,), jnp.float32),
        names=("selective_scan_fwd", "selective_scan_bwd"))
    assert count == 2


@pytest.mark.parametrize("window", [None, 512], ids=["causal", "window512"])
def test_flash_at_64_128_fwd_bwd_at_the_phi4_cells_size(
        one_chip, compiled_kernels, window):
    """40 heads x 8,192 positions, queries and keys 64 wide, values 128
    (differential attention's paired heads), causal and under the window
    of 512, at the blocks the op's rule gives them: 512 x 1,024 for the
    causal call (3 MiB a side) and 512 x 512 under the window, whose
    k-block the rule cuts to the window's width."""
    from paddle_tpu.ops.pallas import flash_attention_mod as fa
    bq, bk = fa._window_blocks(window, *fa._blocks_that_fit(
        8192, 64, 128, 2, 512, 1024))
    assert (bq, bk) == ((512, 512) if window else (512, 1024))

    def f(q, k, v):
        if window:
            return fa._flash_win(q, k, v, window, 0.125, bq, bk)
        return fa._flash(q, k, v, None, "none", jnp.zeros((2,), jnp.int32),
                         True, 0.125, bq, bk, 0.0)

    qk, v = ((1, 40, 8192, 64), jnp.bfloat16), ((1, 40, 8192, 128),
                                                jnp.bfloat16)
    names = ("flash_win_fwd", "flash_win_bwd") if window \
        else ("flash_fwd", "flash_bwd")
    assert _compile(_grad_sum(f, argnums=(0, 1, 2)), one_chip, qk, qk, v,
                    names=names) == 2


def test_the_phi4_cells_step_holds_its_kernels_and_fits(one_chip,
                                                        compiled_kernels,
                                                        monkeypatch):
    """The last four blocks of ``phi4_mini_flash.causal_pretrain`` (source
    layers 16-19: the Mamba layer that gives the memory, the full attention
    that gives K and V, the memory unit and the cross attention that read
    them: both hand-overs across recomputed blocks) at the cell's widths and
    1 x 8,192 tokens, through the cell's own family file: the step compiles
    for the chip with the scan, convolution, flash and layer-norm kernels
    taken (counts and sizes, no time: nothing runs; the window layer's call
    is the case above, and all six blocks ran on the chip, PERF.md section
    6, PR 50: four keep this case's compile under a minute)."""
    from paddle_tpu import monitor
    prefixes = ("selective_scan", "causal_conv1d", "flash_attention",
                "recompute")
    before = {p: monitor.snapshot(p) for p in prefixes}
    seen = _cell_step_memory(monkeypatch, one_chip,
                             "phi4_mini_flash.causal_pretrain", 4, None,
                             first_layer=16)

    def gained(prefix, name):
        return monitor.snapshot(prefix).get(f"{prefix}.{name}", 0) \
            - before[prefix].get(f"{prefix}.{name}", 0)

    assert gained("selective_scan", "kernel_traced") == 1
    assert gained("selective_scan", "xla_traced") == 0
    assert gained("causal_conv1d", "kernel_traced") == 1
    assert gained("flash_attention", "kernel_traced") == 2
    # the memory to the memory unit, K and V to the cross attention
    assert gained("recompute", "handed_on") == 3
    assert gained("recompute", "handed_on_bytes") == 2 * 8192 * (
        5120 + 20 * 64 + 10 * 128)
    # 538 M parameters at 12 bytes (weights and two moments; the gradient
    # is a temporary), and temporaries that leave the chip's 16 GiB room
    params = 41_241_600 + 19_668_864 + 26_214_400 + 13_112_704 \
        + 4 * 78_653_440 + 25_008 * 2_560 + 5_120
    assert abs(seen.argument_size_in_bytes - 12 * params) < 2 ** 20
    assert seen.argument_size_in_bytes + seen.temp_size_in_bytes \
        < 14 * 10 ** 9
