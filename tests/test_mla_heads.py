"""``F.mla_heads``: a latent attention's three projected arrays to the
attention op's heads (the interleaved rotation of the rotary lanes, the
one rotary key head behind every head's ``k_nope``, K split from V,
``[B, S, H d]`` -> ``[B, H, S, d]``), the kernel pair of
``ops/pallas/mla_heads.py`` (interpreted here) against the portable
composition, which is what ``MultiHeadLatentAttention.qkv`` wrote down
before the op was there. Small shapes: the whole file runs in seconds.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor, nn
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import manip, nn_ops
from paddle_tpu.ops import pallas as P
from paddle_tpu.ops.pallas import mla_heads as K

F32, BF16 = jnp.float32, jnp.bfloat16
ROPE = K.ROPE
NAMES = ("q", "k", "v", "dq", "dkv", "dk_rope")


@pytest.fixture()
def kernels_forced():
    P.configure(mla_heads=True)
    try:
        yield
    finally:
        P.configure(mla_heads=None)


def _traced():
    seen = monitor.snapshot("mla_heads")
    return (int(seen.get("mla_heads.kernel_traced", 0)),
            int(seen.get("mla_heads.xla_traced", 0)))


def _rel(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _f32(t):
    return np.asarray(t, np.float32)


def _inputs(batch, s, heads, nope, v, dtype, rope=ROPE, seed=0):
    """The three projected arrays and a gradient for each result."""
    k = jax.random.split(jax.random.key(seed), 6)
    shapes = [(batch, s, heads * (nope + rope)),
              (batch, s, heads * (nope + v)), (batch, s, rope),
              (batch, heads, s, nope + rope), (batch, heads, s, nope + rope),
              (batch, heads, s, v)]
    arrays = [jax.random.normal(ki, shape).astype(dtype)
              for ki, shape in zip(k, shapes)]
    return tuple(arrays[:3]), tuple(arrays[3:])


def _attrs(heads, nope, v, rope=ROPE, theta=1e4):
    return dict(heads=heads, nope=nope, v=v, freq=tuple(
        nn_ops._rotary_frequencies(rope, theta, "test").tolist()))


@functools.lru_cache(maxsize=None)
def _program(fn, heads, nope, v):
    """One compiled program a path and shape: the three results and every
    gradient; cases that differ in their data share it."""
    attrs = _attrs(heads, nope, v)

    def both(gs, *xs):
        y, vjp = jax.vjp(lambda *a: fn(*a, **attrs), *xs)
        return tuple(y) + vjp(tuple(gs))
    return jax.jit(both)


def _six(fn, xs, gs, heads, nope, v):
    return _program(fn, heads, nope, v)(gs, *xs)


# rows: 256 is two tiles of 128 a sequence; 32 heads of 128 + 64 with a
# value of 128 are the joyai cell's widths; 256 lanes without positions
# or of value are two lane tiles a head
KERNEL_CASES = [
    (2, 256, 4, 128, 128, BF16),
    (2, 256, 4, 128, 128, F32),
    (1, 128, 32, 128, 128, BF16),
    (1, 128, 2, 256, 128, BF16),
    (1, 256, 2, 128, 256, BF16),
]
CASE_IDS = [f"{b}x{s}x{h}x({n}+64|{n}+{v})-{'f32' if dt == F32 else 'bf16'}"
            for b, s, h, n, v, dt in KERNEL_CASES]


@pytest.mark.parametrize("batch,s,heads,nope,v,dtype", KERNEL_CASES,
                         ids=CASE_IDS)
def test_kernels_are_the_composition_forward_and_every_gradient(
        batch, s, heads, nope, v, dtype):
    xs, gs = _inputs(batch, s, heads, nope, v, dtype)
    assert K.supported(*(x.shape for x in xs), heads, nope, v, [dtype] * 3)
    got = _six(K.mla_heads, xs, gs, heads, nope, v)
    want = _six(nn_ops._mla_heads, xs, gs, heads, nope, v)
    # the same float32 expressions rounded at the same places: a value
    # moves by the last of its places where a compiler fused a product
    # into a sum on one side
    bound = 2e-6 if dtype == F32 else 2.0 ** -8
    for name, a, b in zip(NAMES, got, want):
        assert a.dtype == b.dtype == dtype and a.shape == b.shape, name
        if name == "dk_rope" and dtype == BF16:
            continue    # the composition's bfloat16 sum: the test below
        assert _rel(a, b) <= bound, name
        if dtype == BF16:   # and most values do not move at all
            assert np.mean(_f32(a) == _f32(b)) > 0.999, name


@pytest.mark.parametrize("batch,s,heads,nope,v,dtype", KERNEL_CASES,
                         ids=CASE_IDS)
def test_lanes_without_positions_are_moved_bit_for_bit(batch, s, heads, nope,
                                                       v, dtype):
    """Forward: ``q_nope``, ``k_nope`` and ``v`` are the projections' own
    values at their new places; backward: their gradients likewise."""
    xs, gs = _inputs(batch, s, heads, nope, v, dtype, seed=1)
    q, k, val, dq, dkv, _ = (_f32(t) for t in _six(
        K.mla_heads, xs, gs, heads, nope, v))
    heads_of = lambda t, d: _f32(t).reshape(batch, s, heads, d).transpose(
        0, 2, 1, 3)
    xq, xkv = heads_of(xs[0], nope + ROPE), heads_of(xs[1], nope + v)
    np.testing.assert_array_equal(q[..., :nope], xq[..., :nope])
    np.testing.assert_array_equal(k[..., :nope], xkv[..., :nope])
    np.testing.assert_array_equal(val, xkv[..., nope:])
    dq, dkv = heads_of(dq, nope + ROPE), heads_of(dkv, nope + v)
    np.testing.assert_array_equal(dq[..., :nope], _f32(gs[0])[..., :nope])
    np.testing.assert_array_equal(dkv[..., :nope], _f32(gs[1])[..., :nope])
    np.testing.assert_array_equal(dkv[..., nope:], _f32(gs[2]))


@pytest.mark.parametrize("route", ["xla", "kernels"])
@pytest.mark.parametrize("heads,theta", [(4, 1e4), (32, 32e6)],
                         ids=["4-heads", "32-heads-the-cells-theta"])
def test_rotary_lanes_are_rotary_embeddings_interleaved_numbers(
        request, route, heads, theta):
    """Every head's rotary lanes of q, and the ONE rotary key head behind
    every head's ``k_nope``."""
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    s, nope, v = 128, 128, 128
    xs, _ = _inputs(1, s, heads, nope, v, BF16, seed=2)
    q, k, _ = F.mla_heads(*(pt.to_tensor(x) for x in xs), heads, nope, v,
                          theta=theta)
    q_rope = pt.to_tensor(xs[0]).reshape([1, s, heads, nope + ROPE])
    want_q = F.rotary_embedding(
        q_rope.transpose([0, 2, 1, 3])[:, :, :, nope:], theta=theta)
    want_k = F.rotary_embedding(pt.to_tensor(xs[2]), theta=theta)
    if route == "xla":
        np.testing.assert_array_equal(q.numpy()[..., nope:], want_q.numpy())
    else:
        assert _rel(q.data[..., nope:], want_q.data) <= 2.0 ** -8
        assert np.mean(q.numpy()[..., nope:] == want_q.numpy()) > 0.999
    for h in range(heads):      # the same 64 lanes in every head
        np.testing.assert_array_equal(k.numpy()[0, h, :, nope:],
                                      k.numpy()[0, 0, :, nope:])
    assert _rel(k.data[0, 0, :, nope:], want_k.data[0]) <= 2.0 ** -8


@pytest.mark.parametrize("heads", [2, 32])
def test_the_key_heads_gradient_is_the_sum_over_the_heads(heads):
    """``d k_rope`` against float32 arithmetic on the same values: the
    heads' gradients added up, then the rotation's transpose. The kernel
    rounds twice (the sum, as the broadcast's backward does, and the
    result), each to eight places."""
    s, nope, v = 128, 128, 128
    xs, gs = _inputs(1, s, heads, nope, v, BF16, seed=3)
    got = _six(K.mla_heads, xs, gs, heads, nope, v)[5]
    want = _six(nn_ops._mla_heads, tuple(x.astype(F32) for x in xs),
                tuple(g.astype(F32) for g in gs), heads, nope, v)[5]
    assert got.dtype == BF16 and want.dtype == F32
    assert _rel(got, want) <= 2.0 ** -7
    # and no single head's: the sum of 32 is well over any one of them
    one = _six(nn_ops._mla_heads, tuple(x.astype(F32) for x in xs),
               tuple(g.astype(F32).at[:, 1:, :, nope:].set(0) if i == 1
                     else g.astype(F32) for i, g in enumerate(gs)),
               heads, nope, v)[5]
    assert _rel(got, one) > 0.25


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_the_op_through_the_tape_counts_the_route_it_traced(request, route):
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    heads, nope, v = 2, 128, 128
    xs, gs = _inputs(1, 128, heads, nope, v, BF16, seed=4)
    leaves = [pt.Tensor(x, stop_gradient=False) for x in xs]
    before = _traced()
    outs = F.mla_heads(*leaves, heads, nope, v, theta=1e4)
    after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) \
        == ((1, 0) if route == "kernels" else (0, 1))
    want = _six(nn_ops._mla_heads, xs, gs, heads, nope, v)
    for name, a, b in zip(NAMES, outs, want):
        assert a.dtype == b.dtype and _rel(a.data, b) <= 2.0 ** -8, name
    if route == "xla":      # its gradients are jax's own, op by op: slow
        return
    sum((o.astype("float32") * pt.Tensor(g.astype(F32))).sum()
        for o, g in zip(outs, gs)).backward()
    for name, leaf, b in zip(NAMES[3:], leaves, want[3:]):
        assert leaf._grad.dtype == b.dtype, name
        assert _rel(leaf._grad, b) <= 2.0 ** -6, name


@pytest.mark.parametrize("s,heads,nope,v,rope,why", [
    (128, 4, 128, 128, 32, "a rotary part of 32 lanes"),
    (128, 4, 64, 128, 64, "64 lanes without positions are half a tile"),
    (128, 4, 128, 64, 64, "a value of 64 lanes"),
    (128, 3, 128, 128, 64, "heads that make no pairs"),
    (200, 4, 128, 128, 64, "200 rows are no whole row tile"),
])
def test_what_the_tiles_do_not_fit_takes_the_composition(
        kernels_forced, s, heads, nope, v, rope, why):
    xs, _ = _inputs(1, s, heads, nope, v, BF16, rope=rope)
    assert not K.supported(*(x.shape for x in xs), heads, nope, v,
                           [BF16] * 3), why
    before = _traced()
    got = F.mla_heads(*(pt.to_tensor(x) for x in xs), heads, nope, v)
    after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1), why
    want = nn_ops._mla_heads(*xs, **_attrs(heads, nope, v, rope))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a.numpy(), _f32(b))


def test_arrays_of_two_dtypes_take_the_composition():
    xs, _ = _inputs(1, 128, 2, 128, 128, BF16)
    shapes = [x.shape for x in xs]
    assert K.supported(*shapes, 2, 128, 128, [BF16] * 3)
    assert not K.supported(*shapes, 2, 128, 128, [BF16, BF16, F32])
    assert not K.supported(*shapes, 2, 128, 128, [jnp.float16] * 3)


def test_across_devices_the_registry_turns_the_kernels_off(monkeypatch):
    """A step whose state spans devices traces under ``gspmd_trace``: the
    kernels' default is off there, and the op takes the composition."""
    monkeypatch.setattr(P, "interpret_mode", lambda: False)     # as on a TPU
    assert P.enabled("mla_heads")
    xs, _ = _inputs(1, 128, 2, 128, 128, BF16)
    with pytest.warns(UserWarning, match="spans 4 devices"), \
            P.gspmd_trace(4):
        assert not P.enabled("mla_heads")
        before = _traced()
        F.mla_heads(*(pt.to_tensor(x) for x in xs), 2, 128, 128)
        after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1)


@pytest.mark.parametrize("q,kv,k_rope,match", [
    ((1, 128, 2 * 192), (1, 128, 2 * 256), (1, 128, 64, 1), "not \\[B, S"),
    ((1, 128, 2 * 192 + 2), (1, 128, 2 * 256), (1, 128, 64), "2 heads of"),
    ((1, 128, 2 * 192), (1, 128, 2 * 192), (1, 128, 64), "2 heads of"),
    ((1, 128, 2 * 191), (1, 128, 2 * 256), (1, 128, 63), "even"),
])
def test_arguments_that_say_nothing_are_refused(q, kv, k_rope, match):
    with pytest.raises(ValueError, match=match):
        F.mla_heads(*(pt.to_tensor(jnp.ones(shape, BF16))
                      for shape in (q, kv, k_rope)), 2, 128, 128)


# -- the layer's call site ---------------------------------------------------

def _parents_chain(layer, x):
    """``MultiHeadLatentAttention.qkv`` as it was before ``F.mla_heads``."""
    b, s, h = x.shape[0], x.shape[1], layer.num_heads
    nope, rope = layer.qk_nope_head_dim, layer.qk_rope_head_dim
    q = layer.q_b_proj(layer.q_a_layernorm(layer.q_a_proj(x)))
    q = q.reshape([b, s, h, nope + rope]).transpose([0, 2, 1, 3])
    ckv = layer.kv_a_proj_with_mqa(x)
    kv = layer.kv_b_proj(layer.kv_a_layernorm(
        ckv[:, :, :layer.kv_lora_rank]))
    kv = kv.reshape([b, s, h, nope + layer.v_head_dim]).transpose(
        [0, 2, 1, 3])
    q_rope = F.rotary_embedding(q[:, :, :, nope:], theta=layer.rope_theta)
    k_rope = F.rotary_embedding(ckv[:, :, layer.kv_lora_rank:],
                                theta=layer.rope_theta)
    k_rope = k_rope.unsqueeze(1).expand([b, h, s, rope])
    q = manip.concat([q[:, :, :, :nope], q_rope], axis=-1)
    k = manip.concat([kv[:, :, :, :nope], k_rope], axis=-1)
    return q, k, kv[:, :, :, nope:]


@pytest.mark.parametrize("route", ["xla", "kernels"])
@pytest.mark.parametrize("widths", [(128, 64, 128), (32, 16, 48)],
                         ids=["128+64|128", "32+16|48"])
def test_heads_of_a_layer_are_the_parents_chain(request, route, widths):
    """The joyai cell's head widths, which the kernels take, and widths
    of no whole tile, which they never see."""
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    nope, rope, v = widths
    layer = nn.MultiHeadLatentAttention(64, 4, 48, 32, nope, rope, v,
                                        rope_theta=32e6)
    x = pt.to_tensor(np.asarray(
        jax.random.normal(jax.random.key(5), (2, 128, 64))))
    before = _traced()
    got = layer.qkv(x)
    after = _traced()
    kernel = route == "kernels" and rope == ROPE
    assert (after[0] - before[0], after[1] - before[1]) \
        == ((1, 0) if kernel else (0, 1))
    for name, a, b in zip("qkv", got, _parents_chain(layer, x)):
        assert tuple(a.shape) == tuple(b.shape), name
        if kernel:
            assert _rel(a.data, b.data) <= 2e-6, name
        else:
            np.testing.assert_array_equal(a.numpy(), b.numpy(), err_msg=name)


def test_a_step_lowers_each_kernel_once_a_shape_however_many_layers_call_it():
    """Two recomputed layers: four forward calls (two replayed) and two
    backward calls of one shape are two ``pallas_call`` instances, because
    each kernel is behind a module-level ``jax.jit`` (what
    ``step_pallas_instances`` counts, and every process's set-up pays);
    a layer outside a checkpoint stages the forward's jit once more."""
    from paddle_tpu.monitor import xla
    heads, nope, v = 2, 128, 128
    xs, _ = _inputs(1, 128, heads, nope, v, BF16)
    attrs = _attrs(heads, nope, v)

    def layer(xq, xkv, kr):
        q, k, val = K.mla_heads(xq, xkv, kr, **attrs)
        back = lambda t: jnp.transpose(t, (0, 2, 1, 3)).reshape(
            t.shape[0], t.shape[2], -1)
        return (back(q) + back(k),
                jnp.concatenate([back(k)[..., :heads * nope], back(val)], -1),
                kr + back(k)[..., -ROPE:])

    def loss(*xs):
        block = jax.checkpoint(layer)
        return sum(t.astype(F32).sum() for t in block(*block(*xs)))

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(*xs)
    assert xla.count_pallas(jaxpr)[0] == 2
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *xs: loss(*layer(*xs)), (0, 1, 2)))(*xs)
    assert xla.count_pallas(jaxpr)[0] == 3
