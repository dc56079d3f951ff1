"""The gated short convolution ``F.gated_short_conv`` (the lfm2 cell's
operator ``c * conv(b * u)`` over ``in_proj``'s result): one op with an XLA
route and a kernel route (``gated_conv_fwd`` / ``gated_conv_bwd`` in
``ops/pallas/causal_conv1d.py``, interpreted here), against the composition
of the ops the repo had, forward and four gradients; where a sequence
starts; what the kernel route traces; which calls take it. No model is
built here (tests/test_lfm2.py has the model and the layer)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu import monitor
from paddle_tpu.nn import functional as F
from paddle_tpu.ops import pallas as P
from paddle_tpu.ops.pallas import causal_conv1d as CK
from paddle_tpu.ops.ssm import _gated_conv
from family_contract import rel as _rel

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture()
def kernels_forced():
    P.configure(gated_short_conv=True)
    try:
        yield
    finally:
        P.configure(gated_short_conv=None)


def _traced():
    seen = monitor.snapshot("gated_short_conv")
    return (int(seen.get("gated_short_conv.kernel_traced", 0)),
            int(seen.get("gated_short_conv.xla_traced", 0)))


def _conv_inputs(batch, seq, channels, taps, dtype, seed=0):
    k = jax.random.split(jax.random.key(seed), 5)
    tap = 1.0 / np.sqrt(taps)
    b, c, u = (jax.random.normal(k[i], (batch, seq, channels)).astype(dtype)
               for i in range(3))
    w = jax.random.uniform(k[3], (channels, taps), F32, -tap, tap)
    return [b, c, u, w], jax.random.normal(k[4], (batch, seq, channels))


def _composition(b, c, u, w):
    """What the op fuses, from the ops the repo had."""
    return c * F.causal_conv1d(b * u, w)


def _op(b, c, u, w):
    from paddle_tpu.ops import manip
    return F.gated_short_conv(manip.concat([b, c, u], axis=-1), w)


def _through_the_tape(fn, arrays, probe):
    leaves = [pt.Tensor(a, stop_gradient=False) for a in arrays]
    out = fn(*leaves)
    (out.astype("float32") * pt.Tensor(probe.astype(F32))).sum().backward()
    return out, [t._grad for t in leaves]


# rows: 256 is two tiles of 128 with B = 2, 384 three; 1024 two of 512;
# channels: 384 is three lane tiles of 128, so a third starts at lane 384
CONV_CASES = [(2, 256, 128, 3), (2, 384, 384, 3), (1, 1024, 128, 3),
              (2, 256, 256, 4), (1, 128, 128, 2)]


@pytest.mark.parametrize("route", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("batch,seq,channels,taps", CONV_CASES)
def test_gated_short_conv_is_the_composition_forward_and_four_gradients(
        request, route, dtype, batch, seq, channels, taps):
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    arrays, probe = _conv_inputs(batch, seq, channels, taps, dtype)
    assert CK.gated_supported((batch, seq, 3 * channels), taps)
    before = _traced()
    out, grads = _through_the_tape(_op, arrays, probe)
    after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) \
        == ((1, 0) if route == "kernels" else (0, 1))
    assert out.dtype == dtype and tuple(out.shape) == (batch, seq, channels)
    # the oracle: the composition on float32 copies of the same values
    f32 = [a.astype(F32) for a in arrays]
    want, want_grads = _through_the_tape(_composition, f32, probe)
    names = ("y", "b", "c", "u", "weight")
    got_all, want_all = [out.data] + grads, [want.data] + want_grads
    if dtype == F32:
        for name, g, w in zip(names, got_all, want_all):
            assert _rel(g, w) < 2e-6, name
        return
    # bfloat16: one rounding on the way out (half a unit in the last of
    # eight places, of the largest value at the most) where the composition
    # rounds at every stage: as near the oracle as that, or as the
    # composition at the call's own dtype is, and no further
    port, port_grads = _through_the_tape(_composition, arrays, probe)
    for name, g, w, p in zip(names, got_all, want_all,
                             [port.data] + port_grads):
        assert g.dtype == p.dtype, name
        assert _rel(g, w) <= max(1.1 * _rel(p, w), 2.0 ** -8) + 1e-4, name


@pytest.mark.parametrize("route", ["xla", "kernels"])
def test_a_sequence_starts_from_zeros_whatever_stands_before_it(request,
                                                                route):
    """B = 2, two row tiles of 128 a sequence: row 0 of sequence 1 (and
    every row of it) is unchanged when sequence 0 changes, forward and
    backward; inside a sequence a row tile's first rows read the tile
    before."""
    if route == "kernels":
        request.getfixturevalue("kernels_forced")
    (b, c, u, w), probe = _conv_inputs(2, 256, 128, 3, F32, seed=7)
    bcx = jnp.concatenate([b, c, u], -1)

    def run(bcx):
        t = pt.Tensor(bcx, stop_gradient=False)
        y = F.gated_short_conv(t, pt.Tensor(w))
        (y * pt.Tensor(probe)).sum().backward()
        return np.asarray(y.data), np.asarray(t._grad)

    y, g = run(bcx)
    other = bcx.at[0].set(jax.random.normal(jax.random.key(9), bcx.shape[1:]))
    y2, g2 = run(other)
    np.testing.assert_array_equal(y[1], y2[1])
    np.testing.assert_array_equal(g[1], g2[1])
    assert np.abs(y[0] - y2[0]).max() > 0.1
    # row 0 of a sequence sees zeros before it: y[0] = c[0] k_{K-1} (b u)[0]
    np.testing.assert_allclose(
        y[1, 0], np.asarray(c[1, 0] * w[:, 2] * b[1, 0] * u[1, 0]),
        rtol=1e-6, atol=1e-7)
    # the tile boundary at row 128: moving row 127 moves rows 127..129
    moved = bcx.at[1, 127].add(1.0)
    delta = np.abs(run(moved)[0][1] - y[1]).max(-1)
    assert delta[:127].max() == 0 and delta[127:130].min() > 0 \
        and delta[130:].max() == 0
    # and a cotangent at row 128 reaches rows 126..128 of d(b) and d(u)
    spike = jnp.zeros_like(probe).at[1, 128].set(1.0)
    t = pt.Tensor(bcx, stop_gradient=False)
    (F.gated_short_conv(t, pt.Tensor(w)) * pt.Tensor(spike)).sum().backward()
    db = np.abs(np.asarray(t._grad)[1, :, :128]).max(-1)
    assert db[:126].max() == 0 and db[126:129].min() > 0 \
        and db[129:].max() == 0
    assert np.abs(np.asarray(t._grad)[0]).max() == 0


def test_the_kernel_route_is_two_kernels_and_no_copy_of_a_third():
    """Forward and backward are one ``pallas_call`` each, behind
    module-level jits; nothing of rows x channels is sliced out of ``bcx``
    or concatenated into its gradient, and nothing is float32 at a
    kernel's boundary but the taps' gradient."""
    bcx = jax.ShapeDtypeStruct((2, 256, 768), BF16)
    w = jax.ShapeDtypeStruct((256, 3), F32)
    dy = jax.ShapeDtypeStruct((2, 256, 256), BF16)

    def both(a, b, ct):
        y, vjp = jax.vjp(CK.gated_short_conv, a, b)
        return y, vjp(ct)

    text = str(jax.make_jaxpr(both)(bcx, w, dy))
    assert text.count("pallas_call") == 2
    assert "name=gated_conv_fwd" in text and "name=gated_conv_bwd" in text
    assert "concatenate" not in text and "slice" not in text.replace(
        "dynamic_slice", "")
    assert "f32[2,256,768]" not in text and "f32[2,256,256]" not in text
    assert "bf16[2,256,768]" in text and "f32[2,3,256]" in text


def test_which_calls_take_the_kernels_is_read_off_the_call(monkeypatch):
    assert P.enabled("gated_short_conv") is False         # a CPU
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    assert P.enabled("gated_short_conv") is True
    monkeypatch.undo()
    assert CK.gated_supported((2, 8192, 6144), 3)          # the cell's
    assert not CK.gated_supported((2, 8192, 6145), 3)
    assert not CK.gated_supported((2, 8192, 3 * 64), 3)    # no lane tile
    assert not CK.gated_supported((2, 100, 384), 3)        # no row tile
    assert not CK.gated_supported((8192, 6144), 3)
    P.configure(gated_short_conv=True)
    try:
        (b, c, u, w), _ = _conv_inputs(1, 100, 128, 3, F32)
        before = _traced()
        got = _op(*(pt.Tensor(a) for a in (b, c, u, w)))
        assert _traced() == (before[0], before[1] + 1)      # portable path
        np.testing.assert_allclose(
            got.numpy(), _gated_conv(jnp.concatenate([b, c, u], -1), w),
            atol=1e-6)
    finally:
        P.configure(gated_short_conv=None)
    with pytest.raises(ValueError, match="three times"):
        F.gated_short_conv(pt.Tensor(jnp.zeros((1, 128, 256))),
                           pt.Tensor(jnp.zeros((128, 3))))
