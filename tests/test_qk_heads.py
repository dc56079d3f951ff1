"""``F.qk_heads``: a projection's result to the attention op's heads (head
norm, half-split rotation, ``[B, S, H D]`` -> ``[B, H, S, D]``), the kernel
pair of ``ops/pallas/qk_heads.py`` (interpreted here) against the portable
composition of the three ops, which is what
``GroupedQueryAttention._heads`` ran before the op was there. Small shapes:
the whole file runs in seconds.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import monitor, nn
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import nn_ops
from paddle_tpu.ops import pallas as P
from paddle_tpu.ops.pallas import qk_heads as K

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture()
def kernels_forced():
    P.configure(qk_heads=True)
    try:
        yield
    finally:
        P.configure(qk_heads=None)


def _traced():
    seen = monitor.snapshot("qk_heads")
    return (int(seen.get("qk_heads.kernel_traced", 0)),
            int(seen.get("qk_heads.xla_traced", 0)))


def _rel(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _both_copies(s):
    """sdar's positions: the noisy copy's rows, then the clean copy's,
    token i of either at position i."""
    return jnp.concatenate([jnp.arange(s // 2, dtype=jnp.int32)] * 2)


def _inputs(batch, s, heads, d, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(k[0], (batch, s, heads * d)).astype(dtype),
            1 + 0.1 * jax.random.normal(k[1], (d,), F32),
            jax.random.normal(k[2], (batch, heads, s, d)).astype(dtype))


def _attrs(heads, d, normed, rotated, positioned, theta=1e4):
    freq = tuple(nn_ops._rotary_frequencies(d, theta, "test").tolist()) \
        if rotated else None
    return dict(heads=heads, epsilon=1e-6, freq=freq, normed=normed,
                positioned=positioned)


def _value_and_grads(fn, attrs, g, *args):
    """One compiled program a path: the result and every gradient."""
    def both(g, *args):
        y, vjp = jax.vjp(lambda *a: fn(*a, **attrs), *args)
        return y, vjp(g)
    return jax.jit(both)(g, *args)


# rows: 256 is two tiles of 128 a sequence, 512 one of 512; a head of 256
# lanes rolls by a whole lane tile
KERNEL_CASES = [
    (2, 256, 4, 128, "norm+rotation@positions", F32),
    (2, 256, 4, 128, "norm+rotation@positions", BF16),
    (1, 512, 2, 128, "norm+rotation", BF16),
    (1, 128, 2, 256, "norm+rotation@positions", BF16),
    (2, 256, 4, 128, "rotation", F32),
    (2, 256, 4, 128, "rotation", BF16),
    (1, 256, 3, 128, "rotation@positions", BF16),
    (2, 256, 4, 128, "norm", BF16),
]


@pytest.mark.parametrize(
    "batch,s,heads,d,what,dtype", KERNEL_CASES,
    ids=[f"{b}x{s}x{h}x{d}-{what}-{'f32' if dt == F32 else 'bf16'}"
         for b, s, h, d, what, dt in KERNEL_CASES])
def test_kernels_are_the_composition_forward_and_every_gradient(
        batch, s, heads, d, what, dtype):
    normed, rotated = "norm" in what, "rotation" in what
    positioned = "positions" in what
    x, w, g = _inputs(batch, s, heads, d, dtype)
    assert K.supported(x.shape, heads, (s,) if positioned else None)
    rest = (w,) * normed + (_both_copies(s),) * positioned
    attrs = _attrs(heads, d, normed, rotated, positioned)
    got, got_grads = _value_and_grads(K.qk_heads, attrs, g, x, *rest)
    want, want_grads = _value_and_grads(nn_ops._qk_heads, attrs, g, x, *rest)
    assert got.dtype == dtype and got.shape == (batch, heads, s, d)
    if positioned:      # whole numbers carry no gradient
        assert got_grads[-1].dtype == want_grads[-1].dtype \
            == jax.dtypes.float0
    names = ("y", "dx") + ("dw",) * normed
    # float32: the order of a head's 128 squares in their sum, no more;
    # bfloat16: the same roundings at the same places, so a value moves by
    # the last of its eight places where a float32 sum fell the other way
    bound = 2e-6 if dtype == F32 else 2.0 ** -8
    for name, a, b in zip(names, (got,) + got_grads, (want,) + want_grads):
        assert a.dtype == b.dtype and a.shape == b.shape, name
        assert _rel(a, b) <= bound, name
    if dtype == BF16:   # and most values do not move at all
        same = np.mean(np.asarray(got, np.float32)
                       == np.asarray(want, np.float32))
        assert same > 0.999


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_the_op_through_the_tape_counts_the_route_it_traced(request, route):
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    x, w, g = _inputs(1, 256, 2, 128, BF16, seed=3)
    leaves = [pt.Tensor(a, stop_gradient=False) for a in (x, w)]
    before = _traced()
    out = F.qk_heads(leaves[0], 2, leaves[1], 1e-6,
                     positions=pt.to_tensor(_both_copies(256)), theta=1e4)
    after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) \
        == ((1, 0) if route == "kernels" else (0, 1))
    want, grads = _value_and_grads(
        nn_ops._qk_heads, _attrs(2, 128, True, True, True), g, x, w,
        _both_copies(256))
    assert out.dtype == want.dtype and _rel(out.data, want) <= 2.0 ** -8
    if route == "xla":      # its gradients are jax's own, op by op: slow
        return
    (out.astype("float32") * pt.Tensor(g.astype(F32))).sum().backward()
    for name, a, b in zip(("dx", "dw"), (leaves[0]._grad, leaves[1]._grad),
                          grads):
        assert a.dtype == b.dtype, name
        assert _rel(a, b) <= 2.0 ** -8, name


@pytest.mark.parametrize("shape,heads,positions,why", [
    ((1, 256, 4 * 64), 4, None, "a head of 64 lanes is half a lane tile"),
    ((1, 200, 2 * 128), 2, None, "200 rows are no whole row tile"),
    ((1, 256, 2 * 128), 2, (1, 256), "positions of more than one axis"),
    ((256, 2 * 128), 2, None, "no batch axis"),
])
def test_what_the_tiles_do_not_fit_takes_the_composition(
        kernels_forced, shape, heads, positions, why):
    assert not K.supported(shape, heads, positions), why
    if len(shape) != 3:
        return
    x = jax.random.normal(jax.random.key(1), shape).astype(BF16)
    at = None if positions is None else pt.to_tensor(
        jnp.arange(256, dtype=jnp.int32)[None])
    before = _traced()
    out = F.qk_heads(pt.to_tensor(x), heads, positions=at, theta=1e4)
    after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1), why
    d = shape[2] // heads
    want = F.rotary_embedding(
        pt.to_tensor(x).reshape([1, shape[1], heads, d]).transpose(
            [0, 2, 1, 3]), at, theta=1e4, interleaved=False)
    np.testing.assert_array_equal(np.asarray(out.data, np.float32),
                                  np.asarray(want.data, np.float32))


def test_across_devices_the_registry_turns_the_kernels_off(monkeypatch):
    """A step whose state spans devices traces under ``gspmd_trace``: the
    kernels' default is off there, and the op takes the composition."""
    monkeypatch.setattr(P, "interpret_mode", lambda: False)     # as on a TPU
    assert P.enabled("qk_heads")
    x, w, _ = _inputs(1, 256, 2, 128, BF16)
    with pytest.warns(UserWarning, match="spans 4 devices"), \
            P.gspmd_trace(4):
        assert not P.enabled("qk_heads")
        before = _traced()
        F.qk_heads(pt.to_tensor(x), 2, pt.to_tensor(w), 1e-6, theta=1e4)
        after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) == (0, 1)


def test_arguments_that_say_nothing_are_refused():
    x = pt.to_tensor(jnp.ones((1, 128, 256), BF16))
    with pytest.raises(ValueError, match="heads"):
        F.qk_heads(x, 3)
    with pytest.raises(ValueError, match="positions without theta"):
        F.qk_heads(x, 2, positions=pt.to_tensor(jnp.arange(128)))


# -- the layer's call site ---------------------------------------------------

def _parents_chain(layer, t, b, s, count, norm, rotate, positions):
    """``GroupedQueryAttention._heads`` as it was before ``F.qk_heads``."""
    t = t.reshape([b, s, count, layer.head_dim])
    if norm is not None:
        t = norm(t)
    t = t.transpose([0, 2, 1, 3])
    if rotate:
        t = F.rotary_embedding(t, positions, theta=layer.rope_theta,
                               interleaved=False)
    r = layer.num_heads // count
    if r == 1:
        return t
    t = t.unsqueeze(2).expand([b, count, r, s, layer.head_dim])
    return t.reshape([b, layer.num_heads, s, layer.head_dim])


@pytest.mark.parametrize("route", ["xla", "kernels"])
@pytest.mark.parametrize("kind", ["norm+rotation", "rotation", "neither"])
def test_heads_of_a_layer_are_the_parents_chain(request, route, kind):
    """sdar's layer (head norms, rotation at given positions), smallthinker's
    window layer (rotation, positions counted) and its global layer or
    nemotron's (neither: the op is not called)."""
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    layer = nn.GroupedQueryAttention(
        64, 4, 2, 128, causal=True,
        qk_norm_epsilon=1e-6 if "norm" in kind else None,
        rope_theta=1e4 if "rotation" in kind else None)
    if layer.q_norm is not None:
        for i, norm in enumerate((layer.q_norm, layer.k_norm)):
            norm.weight.set_value(np.asarray(1 + 0.1 * jax.random.normal(
                jax.random.key(i), (128,)), np.float32))
    x = pt.to_tensor(np.asarray(
        jax.random.normal(jax.random.key(5), (2, 256, 64))))
    at = pt.to_tensor(_both_copies(256)) if "norm" in kind else None
    rotate = layer.rope_theta is not None
    before = _traced()
    got = layer.qkv(x, at)
    after = _traced()
    calls = 0 if kind == "neither" else 2       # q and k; never v
    assert (after[0] - before[0], after[1] - before[1]) \
        == ((calls, 0) if route == "kernels" else (0, calls))
    want = [_parents_chain(layer, layer.q_proj(x), 2, 256, 4, layer.q_norm,
                           rotate, at),
            _parents_chain(layer, layer.k_proj(x), 2, 256, 2, layer.k_norm,
                           rotate, at),
            _parents_chain(layer, layer.v_proj(x), 2, 256, 2, None, False,
                           None)]
    for name, a, b in zip("qkv", got, want):
        assert tuple(a.shape) == (2, 4, 256, 128), name
        if route == "xla" or name == "v":
            np.testing.assert_array_equal(a.numpy(), b.numpy(), err_msg=name)
        else:
            assert _rel(a.data, b.data) <= 2e-6, name


def test_a_step_lowers_each_kernel_once_a_shape_however_many_layers_call_it():
    """Two recomputed layers: four forward calls (two replayed) and two
    backward calls of one shape are two ``pallas_call`` instances, because
    each kernel is behind a module-level ``jax.jit`` (what
    ``step_pallas_instances`` counts, and every process's set-up pays);
    a layer outside a checkpoint stages the forward's jit once more."""
    from paddle_tpu.monitor import xla
    x, w, _ = _inputs(1, 256, 2, 128, BF16)
    attrs = _attrs(2, 128, True, True, False)

    def layer(x, w):
        heads = K.qk_heads(x, w, **attrs)
        return jnp.transpose(heads, (0, 2, 1, 3)).reshape(x.shape)

    def loss(x, w):
        block = jax.checkpoint(layer)
        return block(block(x, w), w).astype(F32).sum()

    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1)))(x, w)
    assert xla.count_pallas(jaxpr)[0] == 2
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x, w: loss(layer(x, w), w), (0, 1)))(x, w)
    assert xla.count_pallas(jaxpr)[0] == 3
