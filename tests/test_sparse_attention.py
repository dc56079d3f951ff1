"""A learned sparse attention's three ops and the layer built on them, at
tiny sizes on the CPU: ``F.dsa_select`` (the definition route and the
kernel in interpret mode, the threshold and its ties), ``flash_attention(
selected=...)`` against ``scaled_dot_product_attention`` under the same
mask, ``F.dsa_indexer_loss`` against ``jax.grad`` of the plain form, and
``nn.SparseGroupedQueryAttention`` against the benchmark's plain float32
reference (benchmark/reference/keye_vl.py). The model built on the layer
is in tests/test_keye_vl.py; compile cases in tests/test_chip_compile.py.
"""
import functools
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
from paddle_tpu import monitor, nn                              # noqa: E402
from paddle_tpu.nn import functional as F                       # noqa: E402
from paddle_tpu.ops import sparse_attention as sa               # noqa: E402
from paddle_tpu.ops.pallas import dsa, flash_attention          # noqa: E402
from paddle_tpu.ops.pallas.flash_attention import _flash_sel    # noqa: E402
from benchmark.reference import keye_vl as R                    # noqa: E402
from family_contract import plain as _plain                     # noqa: E402

SEQ = 24


@functools.lru_cache(maxsize=None)
def _operands(b=2, h=4, s=128, d=16, hi=4, di=8, seed=0):
    """(q, k, v [B, H, S, D], qI [B, Hi, S, Di], kI [B, S, Di], w [B, S,
    Hi]) from a seed."""
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    return (normal(b, h, s, d), normal(b, h, s, d), normal(b, h, s, d),
            normal(b, hi, s, di), normal(b, s, di), normal(b, s, hi))


def _dense_scores(qi, ki, w):
    """I [B, S, S], every pair, the heads added in the op's order."""
    return jax.vmap(sa.index_scores)(qi, ki, w)


# -- the selection -------------------------------------------------------------

def _plain_pack(sel):
    """``sa._pack`` by its definition, element by element in numpy: bit b
    of element [r, c] is key b * width + c."""
    s = sel.shape[-1]
    width = sa.packed_width(s)
    padded = np.zeros((*sel.shape[:-1], sa.PACK * width), np.uint8)
    padded[..., :s] = sel
    bits = padded.reshape(*sel.shape[:-1], sa.PACK, width)
    weights = (1 << np.arange(sa.PACK, dtype=np.uint8))[:, None]
    return (bits * weights).sum(-2).astype(np.uint8).view(np.int8)


# S 1,024 is the least the kernel takes at chunks of 128 keys (eight whole
# chunks share a packed element); 24 and 100 (no whole element) are the
# definition route's alone
@pytest.mark.parametrize("s", [1024, 24, 100])
@pytest.mark.parametrize("case", ["random", "ties", "short_rows"])
def test_pack_and_unpack_are_each_others_inverse(case, s):
    top_k = 8
    if case == "random":
        sel = np.random.default_rng(s).random((2, 1, s, s)) < 0.3
    else:
        _, _, _, qi, ki, w = _operands(b=1, s=s)
        if case == "ties":      # three equal keys: rows with more than top_k
            ki = ki.at[:, 7].set(ki[:, 3]).at[:, 11].set(ki[:, 3])
            top_k = 4
        else:                   # the first rows keep every causal key
            top_k = s // 2
        sel = np.asarray(sa._unpack(sa._select(qi, ki, w, top_k=top_k)[0],
                                    s)) != 0
        kept = sel[0, 0].sum(-1)
        if case == "ties":
            assert kept.max() > top_k
        else:
            assert (kept[:top_k] == np.arange(1, top_k + 1)).all()
    assert dsa.select_supported((1, 4, s, 8), 32, 128) == (s == 1024)
    packed = sa._pack(jnp.asarray(sel))
    assert packed.dtype == jnp.int8 and packed.shape == (
        *sel.shape[:-1], sa.packed_width(s))
    assert np.array_equal(np.asarray(packed), _plain_pack(sel))
    back = sa._unpack(packed, s)
    assert back.dtype == jnp.int8 and np.array_equal(np.asarray(back), sel)


@pytest.mark.parametrize("top_k", [16, 1100], ids=["k16", "k_past_s"])
def test_select_kernel_and_definition_route_give_one_selection(top_k):
    _, _, _, qi, ki, w = _operands(s=1024)
    want = sa._select(qi, ki, w, top_k=top_k)
    got = dsa.select(qi, ki, w, top_k=top_k, rows=32, chunk=128)
    # the same PACKED array from both routes, and the op's int8 selection
    assert got[0].dtype == jnp.int8 and got[0].shape == (2, 1, 1024, 128)
    for name, a, b in zip(("bits", "lse", "tau", "pairs"), got, want):
        a, b = np.asarray(a), np.asarray(b)
        if name in ("bits", "pairs"):
            assert np.array_equal(a, b), name
        else:
            assert np.array_equal(np.isfinite(a), np.isfinite(b)), name
            np.testing.assert_allclose(np.where(np.isfinite(a), a, 0.0),
                                       np.where(np.isfinite(b), b, 0.0),
                                       atol=3e-6, err_msg=name)
    selected = F.dsa_select(*(pt.to_tensor(np.asarray(t))
                              for t in (qi, ki, w)), top_k)[0].numpy()
    assert selected.dtype == np.int8 and np.array_equal(
        selected, np.asarray(sa._unpack(got[0], 1024)))
    sel = selected[:, 0] != 0
    scores = np.asarray(_dense_scores(qi, ki, w))
    s = sel.shape[-1]
    assert not np.triu(sel, 1).any()            # never a key ahead
    kept, tau = sel.sum(-1), np.asarray(got[2])
    k = min(top_k, s)
    # exactly top_k but where scores tie at the threshold (four heads all
    # under the ReLU give an exact 0)
    exact = tau != 0.0
    assert exact.mean() > 0.8
    assert (kept == np.minimum(np.arange(s) + 1, k))[exact].all()
    assert (kept >= np.minimum(np.arange(s) + 1, k)).all()
    # the kept keys are the best ones: none left out beats one kept
    causal = np.tril(np.ones((s, s), bool))
    worst_kept = np.where(sel, scores, np.inf).min(-1)
    best_left = np.where(causal & ~sel, scores, -np.inf).max(-1)
    assert (best_left <= worst_kept).all()
    # lse is the selected scores' log-sum-exp
    want_lse = jax.nn.logsumexp(jnp.where(sel, scores, -jnp.inf), -1)
    np.testing.assert_allclose(np.asarray(got[1]), want_lse, atol=1e-5)
    assert (np.asarray(got[3]) == kept.sum(-1)).all()


@pytest.mark.parametrize("route", ["xla", "kernel"])
def test_tied_scores_keep_every_key_at_the_threshold(route):
    """Keys 3, 7 and 11 are one vector: their scores are equal in every
    row. Where they are the threshold the row keeps all three, so it holds
    more than ``top_k`` keys; ``-0.0`` and ``0.0`` are one score."""
    s = 128 if route == "xla" else 1024
    _, _, _, qi, ki, w = _operands(b=1, s=s)
    ki = ki.at[:, 7].set(ki[:, 3]).at[:, 11].set(ki[:, 3])
    w = jnp.abs(w).at[:, :, 0].multiply(-1.0)       # a head that gives -0.0
    top_k = 4
    select = sa._select if route == "xla" else functools.partial(
        dsa.select, rows=32, chunk=128)
    bits, _, tau, pairs = select(qi, ki, w, top_k=top_k)
    sel = np.asarray(sa._unpack(bits, s))[0, 0] != 0
    assert not np.triu(sel, 1).any()
    # the three are kept together or not at all, in every row that sees them
    assert (sel[11:, 3] == sel[11:, 7]).all()
    assert (sel[11:, 3] == sel[11:, 11]).all()
    assert sel[11:, 3].any()
    # and a row in which they are the threshold holds more than top_k keys
    kept = sel.sum(-1)
    assert (kept[top_k:] >= top_k).all() and kept.max() > top_k
    assert int(np.asarray(pairs)[0]) == sel.sum()


def test_select_through_the_op_has_no_gradient_and_counts_its_route():
    _, _, _, qi, ki, w = _operands(b=1, s=32)
    before = monitor.snapshot("dsa.select")
    tensors = [pt.to_tensor(np.asarray(t)) for t in (qi, ki, w)]
    for t in tensors:
        t.stop_gradient = False
    selected, lse, tau, pairs = F.dsa_select(*tensors, 8)
    assert selected.dtype == jnp.int8 and tuple(selected.shape) == \
        (1, 1, 32, 32)
    assert all(t.stop_gradient for t in (selected, lse, tau, pairs))
    after = monitor.snapshot("dsa.select")
    # one route and one name a traced call
    for counter in ("dsa.select.xla_traced", "dsa.select.results_named"):
        assert after.get(counter, 0) == before.get(counter, 0) + 1, counter
    with pytest.raises(ValueError, match="dsa_select"):
        F.dsa_select(tensors[0], tensors[1], tensors[1], 8)


# -- attention under a selection ------------------------------------------------

def _selection(top_k=24):
    """``(selected int8 [B, 1, S, S], lse, tau, pairs)`` as the op gives
    them."""
    _, _, _, qi, ki, w = _operands()
    bits, *rest = sa._select(qi, ki, w, top_k=top_k)
    return (sa._unpack(bits, qi.shape[2]), *rest)


def _sdpa(q, k, v, sel):
    return F.scaled_dot_product_attention(
        pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v),
        attn_mask=pt.to_tensor(np.asarray(sel) != 0)).data


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_flash_under_a_selection_is_sdpa_under_the_same_mask(route):
    q, k, v = _operands()[:3]
    sel, _, _, _ = _selection()
    g = jnp.asarray(np.random.default_rng(9).normal(size=q.shape),
                    jnp.float32)

    def mine(q, k, v):
        if route == "kernels":      # the smallest tiles: 32 x 64
            return _flash_sel(q, k, v, sel, None, 32, 64)[0]
        return sa.selected_attention(q, k, v, sel)[0]

    want, back = jax.vjp(lambda q, k, v: _sdpa(q, k, v, sel), q, k, v)
    got, mine_back = jax.vjp(mine, q, k, v)
    np.testing.assert_allclose(got, want, atol=3e-6)
    for name, a, b in zip("qkv", mine_back(g), back(g)):
        np.testing.assert_allclose(a, b, atol=1e-5, err_msg="d" + name)


def test_flash_op_takes_the_selection_checks_it_and_returns_the_statistics():
    q, k, v = (pt.to_tensor(np.asarray(t)) for t in _operands()[:3])
    sel, _, _, _ = _selection()
    selected = pt.to_tensor(np.asarray(sel))
    before = monitor.snapshot("flash_attention.selected")
    o, m, l = flash_attention(q, k, v, causal=True, selected=selected,
                              force=True)
    after = monitor.snapshot("flash_attention.selected")
    walked = after["flash_attention.selected_tiles_walked"] \
        - before.get("flash_attention.selected_tiles_walked", 0)
    assert walked == after["flash_attention.selected_tiles_causal"] \
        - before.get("flash_attention.selected_tiles_causal", 0) > 0
    assert tuple(m.shape) == tuple(l.shape) == (8, 1, 128)
    assert m.stop_gradient and l.stop_gradient
    o2, m2, l2 = flash_attention(q, k, v, causal=True,
                                 selected=selected)      # the XLA route
    np.testing.assert_allclose(o.numpy(), o2.numpy(), atol=3e-6)
    np.testing.assert_allclose(m.numpy(), m2.numpy(), atol=3e-6)
    np.testing.assert_allclose(l.numpy(), l2.numpy(), rtol=1e-5)
    for bad in (dict(causal=False, selected=selected),
                dict(causal=True, selected=selected, window=8),
                dict(causal=True, selected=selected[:, :, :64])):
        with pytest.raises(ValueError, match="selected"):
            flash_attention(q, k, v, **bad)


# -- the indexer's loss ---------------------------------------------------------

def _plain_kl(q, k, sel, qi, ki, w):
    """L_I as one expression over whole [S, S] arrays."""
    b, _, s, d = q.shape
    keep = sel != 0
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    target = jnp.mean(jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), -1),
                      1)
    log_r = jax.nn.log_softmax(
        jnp.where(keep[:, 0], _dense_scores(qi, ki, w), -jnp.inf), -1)
    seen = target > 0
    return jnp.sum(jnp.where(
        seen, target * (jnp.log(jnp.where(seen, target, 1.0))
                        - jnp.where(seen, log_r, 0.0)), 0.0)) / (b * s)


def test_indexer_loss_and_its_gradients_are_jax_grad_of_the_plain_form():
    q, k, v, qi, ki, w = _operands()
    sel, lse, _, _ = _selection()
    _, m, l = sa.selected_attention(q, k, v, sel)
    want, want_grads = jax.value_and_grad(
        lambda *a: _plain_kl(q, k, sel, *a), (0, 1, 2))(qi, ki, w)

    def mine(qi, ki, w, q, k):
        return 3.0 * sa._indexer_loss(q, k, m, l, sel, qi, ki, w, lse,
                                      None, False)

    got, grads = jax.value_and_grad(mine, (0, 1, 2, 3, 4))(qi, ki, w, q, k)
    assert abs(float(got) - 3.0 * float(want)) < 1e-5
    for name, a, b in zip(("qi", "ki", "w"), grads, want_grads):
        np.testing.assert_allclose(a, 3.0 * b, atol=2e-7, err_msg=name)
    # the target's side gets nothing
    assert float(jnp.abs(grads[3]).max()) == float(jnp.abs(grads[4]).max()) \
        == 0.0


def test_indexer_loss_through_the_op_keeps_its_pass_under_a_checkpoint():
    """Through ``F.dsa_indexer_loss`` and the tape; and under
    ``jit.recompute``'s policy the pass's results are kept by name, so the
    backward's replay has no second pass (one ``exp`` of the heads' scores
    in the whole gradient program)."""
    from paddle_tpu.memory_plan import checkpoint_policy, KERNEL_RESULTS
    q, k, v, qi, ki, w = _operands()
    sel, lse, _, _ = _selection()
    _, m, l = sa.selected_attention(q, k, v, sel)
    before = monitor.snapshot("dsa.kl")
    tensors = [pt.to_tensor(np.asarray(t))
               for t in (q, k, m, l, sel, qi, ki, w, lse)]
    for i in (5, 6, 7):
        tensors[i].stop_gradient = False
    loss = F.dsa_indexer_loss(*tensors)
    loss.backward()
    want = jax.grad(lambda a: _plain_kl(q, k, sel, a, ki, w))(qi)
    np.testing.assert_allclose(tensors[5]._grad, want, atol=2e-7)
    assert monitor.snapshot("dsa.kl")["dsa.kl.xla_traced"] \
        == before.get("dsa.kl.xla_traced", 0) + 1

    def block(qi, ki, w):
        return sa._indexer_loss(q, k, m, l, sel, qi, ki, w, lse, None, False)

    kept = jax.checkpoint(block, policy=checkpoint_policy(KERNEL_RESULTS))
    whole = jax.checkpoint(block)
    count = lambda f: str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(
        qi, ki, w)).count("dsa_kl_results")
    assert count(kept) > 0
    text = lambda f: str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(qi, ki, w))
    assert text(whole).count(" exp ") > text(kept).count(" exp ")


def test_a_recomputed_block_keeps_the_selections_bits_and_selects_once():
    """A block that selects, attends and takes the indexer's loss, as
    ``jit.recompute`` runs one (the ops under ``no_grad``, JAX
    differentiating the block whole): under its policy the gradient program
    bears the selection's name and holds ONE selection (one sort on this
    route), the replay unpacking the kept bits; a checkpoint with no policy
    selects again."""
    from paddle_tpu.memory_plan import checkpoint_policy, KERNEL_RESULTS
    operands = _operands()
    T = pt.Tensor

    def block(q, k, v, qi, ki, w):
        with pt.no_grad():
            selected, lse, _, _ = F.dsa_select(T(qi), T(ki), T(w), 24)
            o, m, l = flash_attention(T(q), T(k), T(v), causal=True,
                                      selected=selected)
            loss = F.dsa_indexer_loss(T(q), T(k), m, l, selected, T(qi),
                                      T(ki), T(w), lse)
        return jnp.sum(o.data) + loss.data

    def program(f):
        return str(jax.make_jaxpr(jax.grad(f, tuple(range(6))))(*operands))

    keeping = jax.checkpoint(block, policy=checkpoint_policy(KERNEL_RESULTS))
    before = monitor.snapshot("dsa.select").get("dsa.select.results_named",
                                                 0)
    kept = program(keeping)
    # traced once: the replay is the forward's jaxpr, not a second trace
    assert monitor.snapshot("dsa.select")["dsa.select.results_named"] \
        == before + 1
    whole = program(jax.checkpoint(block))
    assert sa.SELECTION_NAMES[0] in kept
    assert (kept.count(" sort["), whole.count(" sort[")) == (1, 2)
    want = jax.grad(block, tuple(range(6)))(*operands)
    got = jax.grad(keeping, tuple(range(6)))(*operands)
    for name, a, b in zip(("q", "k", "v", "qi", "ki", "w"), got, want):
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)


# -- the layer -------------------------------------------------------------------

def _positions(seq=SEQ):
    """Three rows that differ: text, then a 3 x 4 image span from position
    6 (``get_rope_index``'s rule), then text."""
    at = np.zeros((3, seq), np.int32)
    at[:, :6] = np.arange(6)
    row, col = np.divmod(np.arange(12), 4)
    at[:, 6:18] = 6 + np.stack([0 * row, row, col])
    at[:, 18:] = 6 + 4 + np.arange(seq - 18)
    return at


def _sparse_layer(seed=11, top_k=8):
    layer = nn.SparseGroupedQueryAttention(
        64, 4, 2, 16, 4, 8, top_k, qk_norm_epsilon=1e-6, rope_theta=1e4,
        rope_sections=(2, 3, 3))
    key = jax.random.key(seed)
    for i, (_, p) in enumerate(layer.named_parameters()):
        p.set_value(0.3 * jax.random.normal(jax.random.fold_in(key, i),
                                            tuple(p.shape)) + (p.ndim == 1))
    return layer


_LAYER_CFG = dict(head_dim=16, num_attention_heads=4, num_key_value_heads=2,
                  rms_norm_eps=1e-6, rope_theta=1e4,
                  rope_scaling={"mrope_section": [2, 3, 3]},
                  sa_config=dict(indexer_num_heads=4, indexer_head_dim=8,
                                 topk=8))


@pytest.mark.parametrize("force", [False, True], ids=["xla", "kernels"])
def test_sparse_attention_layer_is_the_references(force):
    layer = _sparse_layer()
    w = {k: p.data for k, p in layer.named_parameters()}
    x = np.asarray(jax.random.normal(jax.random.key(12), (1, SEQ, 64)))
    at = _positions()
    got, loss = layer(pt.to_tensor(x), positions=pt.to_tensor(at),
                      force_flash=force)
    want, want_loss = R.attention(_LAYER_CFG, w, jnp.asarray(x[0]),
                                  jnp.asarray(at), _plain)
    np.testing.assert_allclose(got.numpy()[0], want, atol=3e-6)
    assert abs(float(loss.numpy()) - float(want_loss)) < 1e-6
    q, k, v = layer.qkv(pt.to_tensor(x), pt.to_tensor(at))
    rq, rk, rv = R.qkv(_LAYER_CFG, w, jnp.asarray(x[0]), jnp.asarray(at),
                       _plain)
    np.testing.assert_allclose(q.numpy()[0], np.moveaxis(rq, 1, 0),
                               atol=3e-6)
    for mine, theirs in ((k, rk), (v, rv)):     # K/V heads repeated twice
        np.testing.assert_allclose(mine.numpy()[0, ::2],
                                   np.moveaxis(theirs, 1, 0), atol=3e-6)
    qi, ki, wi = layer.indexer(pt.to_tensor(x), pt.to_tensor(at))
    ri, rk, rw = R.indexer(_LAYER_CFG, w, jnp.asarray(x[0]),
                           jnp.asarray(at), _plain)
    np.testing.assert_allclose(qi.numpy()[0], np.moveaxis(ri, 1, 0),
                               atol=3e-6)
    np.testing.assert_allclose(ki.numpy()[0], rk, atol=3e-6)
    np.testing.assert_allclose(wi.numpy()[0], rw, atol=3e-6)


def test_the_layers_selection_is_every_causal_key_then_topk_and_never_ahead():
    """A row with ``t + 1 <= top_k`` sees every causal key, a later row
    exactly ``top_k`` (more only where scores tie at the threshold: four
    heads all under the ReLU give an exact 0), and never a key ahead."""
    layer = _sparse_layer()
    x = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(3),
                                                  (2, SEQ, 64))))
    qi, ki, w = layer.indexer(x, pt.to_tensor(_positions()))
    selected, _, tau, pairs = F.dsa_select(qi, ki, w, 8)
    sel = selected.numpy()[:, 0] != 0
    assert not np.triu(sel, 1).any()
    kept, tau = sel.sum(-1), tau.numpy()
    assert np.isneginf(tau[:, :8]).all() and np.isfinite(tau[:, 8:]).all()
    for t in range(SEQ):
        exact = tau[:, t] != 0.0
        assert (kept[exact, t] == min(t + 1, 8)).all(), t
        assert (kept[~exact, t] >= 8).all(), t
    assert exact.any()
    assert (pairs.numpy() == kept.sum(-1)).all()
    # a later token moves no earlier row's output; position rows that
    # differ move the rows from the span on
    at = _positions()
    base = layer(x, pt.to_tensor(at))[0].numpy()
    later = x.numpy().copy()
    later[:, 15] += 1.0
    moved = np.abs(layer(pt.to_tensor(later), pt.to_tensor(at))[0].numpy()
                   - base).max(-1)
    assert moved[:, :15].max() == 0.0 and moved[:, 15].min() > 0.0
    temporal = pt.to_tensor(np.stack([at[0]] * 3))
    moved = np.abs(layer(x, temporal)[0].numpy() - base).max(-1)
    assert moved[:, :7].max() == 0.0 and moved[:, 8:].min() > 0.0


def test_the_sparse_layer_shares_the_dense_layers_projections_and_heads():
    """Built on ``GroupedQueryAttention``: the same four projections, head
    norms and ``qkv``; with ``topk`` past the sequence it is the dense
    causal layer under the same positions."""
    sparse = _sparse_layer(top_k=SEQ)
    dense = nn.GroupedQueryAttention(64, 4, 2, 16, qk_norm_epsilon=1e-6,
                                     rope_theta=1e4, rope_sections=(2, 3, 3))
    assert isinstance(sparse, nn.GroupedQueryAttention)
    theirs = dict(sparse.named_parameters())
    for name, p in dense.named_parameters():
        p.set_value(theirs[name].data)
    assert set(theirs) - set(dict(dense.named_parameters())) == {
        "indexer_q.weight", "indexer_k.weight", "indexer_k_norm.weight",
        "indexer_k_norm.bias", "indexer_w.weight"}
    x = pt.to_tensor(np.asarray(jax.random.normal(jax.random.key(5),
                                                  (1, SEQ, 64))))
    at = pt.to_tensor(_positions())
    np.testing.assert_allclose(sparse(x, at)[0].numpy(),
                               dense(x, positions=at).numpy(), atol=2e-6)
