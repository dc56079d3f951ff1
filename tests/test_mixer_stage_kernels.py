"""The Mamba mixer's two element-wise stages as Pallas kernel pairs
(ops/pallas/causal_conv1d.py, ops/pallas/gated_rms_norm.py), in interpret
mode on the CPU, against the portable paths they stand in for
(``ops/ssm.py: _conv1d``, ``ops/nn_ops.py: _rms_norm``): forward and every
gradient in float32 and bfloat16; the rows a tile reads of its
neighbours; which calls take the kernels and which keep the portable
path; and that nothing of rows x channels is float32 at a kernel's
boundary.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as pt                                     # noqa: E402
from paddle_tpu import monitor                              # noqa: E402
from paddle_tpu.nn import functional as F                   # noqa: E402
from paddle_tpu.ops import pallas as P                      # noqa: E402
from paddle_tpu.ops.nn_ops import _rms_norm                 # noqa: E402
from paddle_tpu.ops.pallas import causal_conv1d as CK       # noqa: E402
from paddle_tpu.ops.pallas import gated_rms_norm as NK      # noqa: E402
from paddle_tpu.ops.ssm import _conv1d                      # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture()
def kernels_forced():
    P.configure(causal_conv1d=True, gated_rms_norm=True)
    try:
        yield
    finally:
        P.configure(causal_conv1d=None, gated_rms_norm=None)


def _traced(prefix, kernel, xla):
    seen = monitor.snapshot(prefix)
    return int(seen.get(kernel, 0)), int(seen.get(xla, 0))


def _conv_traced():
    return _traced("causal_conv1d", "causal_conv1d.kernel_traced",
                   "causal_conv1d.xla_traced")


def _norm_traced():
    return _traced("rms_norm", "rms_norm.gated_kernel_traced",
                   "rms_norm.gated_xla_traced")


def _took(count, before):
    after = count()
    return after[0] - before[0], after[1] - before[1]


def _gap(got, want):
    got, want = (np.asarray(t, np.float32) for t in (got, want))
    assert got.shape == want.shape
    return np.abs(got - want).max() / (np.abs(want).max() + 1e-30)


def _through_the_tape(op, arrays, probe):
    """(result, gradients) of ``sum(op(*arrays) * probe)`` by the tape:
    the path ``loss.backward()`` takes through ``dispatch.apply``."""
    leaves = [pt.Tensor(a, stop_gradient=False) for a in arrays]
    out = op(*leaves)
    (out.astype("float32") * pt.Tensor(probe.astype(F32))).sum().backward()
    return out, [t._grad for t in leaves]


def _oracle(fn, arrays, probe):
    """The portable path on float32 copies of the same values."""
    f32 = [a.astype(F32) for a in arrays]
    loss = lambda *a: jnp.sum(fn(*a) * probe.astype(F32))   # noqa: E731
    return fn(*f32), jax.grad(loss, argnums=tuple(range(len(f32))))(*f32)


def _same_dtype(fn, arrays, probe):
    """The portable path at the call's own dtypes: what the kernels may be
    as far from the float32 oracle as, and no further."""
    loss = lambda *a: jnp.sum(fn(*a).astype(F32)            # noqa: E731
                              * probe.astype(F32))
    return fn(*arrays), jax.grad(loss,
                                 argnums=tuple(range(len(arrays))))(*arrays)


def _hold(names, got, want, portable, dtype):
    """float32: the portable path's numbers to rounding. bfloat16: each
    result as near the float32 oracle as the portable path's own."""
    for name, g, w, p in zip(names, got, want, portable):
        if dtype == F32:
            assert _gap(g, w) < 2e-6, name
        else:
            assert g.dtype == p.dtype, name
            assert _gap(g, w) <= 1.1 * _gap(p, w) + 1e-4, name


# -- the convolution ---------------------------------------------------------

def _conv_inputs(batch, seq, channels, taps, dtype, bias, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    tap = 1.0 / np.sqrt(taps)
    arrays = [jax.random.normal(k[0], (batch, seq, channels)).astype(dtype),
              jax.random.uniform(k[1], (channels, taps), F32, -tap, tap)]
    if bias:
        arrays.append(jax.random.uniform(k[2], (channels,), F32, -tap, tap))
    return arrays, jax.random.normal(k[3], (batch, seq, channels))


# rows: 384 is three tiles of 128, 512 one tile, 1024 two of 512; channels:
# 256 is one lane tile, 384 three of 128
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("batch,seq,channels,taps,activation,bias", [
    (2, 384, 256, 4, "silu", True),
    (1, 384, 128, 4, None, True),
    (1, 256, 384, 4, "silu", False),
    (1, 128, 128, 4, None, False),
    (1, 1024, 128, 2, "silu", True),
    (1, 384, 128, 9, "silu", True),
    (1, 128, 128, 1, "silu", True),
], ids=["3tiles-silu-bias", "3tiles-linear-bias", "3lanetiles-silu-nobias",
        "1tile-linear-nobias", "2x512-K2", "K9", "K1"])
def test_conv_kernels_equal_the_portable_path_forward_and_every_gradient(
        kernels_forced, batch, seq, channels, taps, activation, bias, dtype):
    arrays, probe = _conv_inputs(batch, seq, channels, taps, dtype, bias)
    assert CK.supported((batch, seq, channels), taps)
    before = _conv_traced()
    out, grads = _through_the_tape(
        lambda *a: F.causal_conv1d(*a, activation=activation), arrays, probe)
    assert _took(_conv_traced, before) == (1, 0)
    assert out.dtype == dtype

    fn = lambda *a: _conv1d(*a, activation=activation)      # noqa: E731
    want, want_grads = _oracle(fn, arrays, probe)
    port, port_grads = _same_dtype(fn, arrays, probe)
    names = ("y", "x", "weight", "bias")[:2 + len(arrays) - 1]
    _hold(names, [out.data] + grads, [want] + list(want_grads),
          [port] + list(port_grads), dtype)


@pytest.mark.parametrize("taps", [2, 4])
def test_conv_reads_its_neighbours_rows_and_zero_outside_the_sequence(
        kernels_forced, taps):
    """A one at the last row of the first tile spreads into the second
    tile's first ``K - 1`` rows by the taps, nothing reaches back; the
    first ``K - 1`` rows of the sequence see zeros in front of them; a
    cotangent at the second tile's first row reaches ``K - 1`` rows back
    into the first tile, and one at the sequence's last row stays in."""
    seq, channels, edge = 256, 128, 127
    w = jnp.arange(1, channels * taps + 1, dtype=F32).reshape(channels, taps)
    x = jnp.zeros((1, seq, channels), F32).at[0, edge].set(1.0)
    y = CK.causal_conv1d(x, w, activation=None)
    for d in range(taps):                   # tap K - 1 - d reads d rows up
        np.testing.assert_array_equal(y[0, edge + d], w[:, taps - 1 - d])
    assert not np.asarray(y[0, :edge]).any()
    assert not np.asarray(y[0, edge + taps:]).any()
    ones = CK.causal_conv1d(jnp.ones((1, seq, channels), F32), w,
                            activation=None)
    for t in range(taps):
        np.testing.assert_allclose(ones[0, t], w[:, taps - 1 - t:].sum(1))

    for row in (edge + 1, seq - 1):
        dy = jnp.zeros((1, seq, channels), F32).at[0, row].set(1.0)
        dx = jax.grad(lambda x: jnp.sum(
            CK.causal_conv1d(x, w, activation=None) * dy))(x)
        for d in range(taps):
            np.testing.assert_array_equal(dx[0, row - d],
                                          w[:, taps - 1 - d])
        assert np.count_nonzero(np.asarray(dx).any(-1)) == taps


# -- the gated grouped norm --------------------------------------------------

def _norm_inputs(batch, seq, width, dtype, scaled, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    arrays = [jax.random.normal(k[0], (batch, seq, width)).astype(dtype),
              jax.random.normal(k[1], (batch, seq, width)).astype(dtype)]
    if scaled:
        arrays.append(1.0 + 0.2 * jax.random.normal(k[2], (width,)))
    return arrays, jax.random.normal(k[3], (batch, seq, width))


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("batch,seq,width,groups,scaled", [
    (2, 384, 1024, 8, True),
    (1, 384, 256, 1, True),
    (1, 128, 256, 2, False),
    (1, 1024, 512, 1, True),
    (1, 256, 2048, 1, True),
], ids=["3tiles-G8", "3tiles-G1", "1tile-G2-noweight", "2x512-G1",
        "2x128-wide-group"])
def test_gated_norm_kernels_equal_the_portable_path_and_every_gradient(
        kernels_forced, batch, seq, width, groups, scaled, dtype):
    arrays, probe = _norm_inputs(batch, seq, width, dtype, scaled)
    assert NK.supported((batch, seq, width), groups)

    def op(y, gate, *weight):
        return F.rms_norm(y, weight[0] if weight else None, 1e-5, groups,
                          gate=gate)

    before = _norm_traced()
    out, grads = _through_the_tape(op, arrays, probe)
    assert _took(_norm_traced, before) == (1, 0)
    assert out.dtype == dtype

    fn = lambda *a: _rms_norm(*a, epsilon=1e-5,             # noqa: E731
                              num_groups=groups, gated=True, scaled=scaled)
    want, want_grads = _oracle(fn, arrays, probe)
    port, port_grads = _same_dtype(fn, arrays, probe)
    names = ("out", "y", "gate", "weight")[:1 + len(arrays)]
    _hold(names, [out.data] + grads, [want] + list(want_grads),
          [port] + list(port_grads), dtype)


def test_a_groups_statistic_is_its_own():
    """Scaling one group's lanes leaves every other group's result as it
    was: a group is a window of lanes, and only its own mean square
    reaches it."""
    arrays, _ = _norm_inputs(1, 128, 1024, F32, True)
    y, z, w = arrays
    base = NK.gated_rms_norm(y, z, w, epsilon=1e-5, num_groups=8)
    scaled = NK.gated_rms_norm(y.at[:, :, 256:384].multiply(100.0), z, w,
                               epsilon=1e-5, num_groups=8)
    same = np.ones(1024, bool)
    same[256:384] = False
    np.testing.assert_array_equal(base[..., same], scaled[..., same])
    np.testing.assert_allclose(base[..., ~same], scaled[..., ~same],
                               rtol=1e-3)       # epsilon is all that differs


# -- what crosses a kernel's boundary ----------------------------------------

def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


@pytest.mark.parametrize("stage", ["conv", "norm"])
def test_nothing_of_rows_x_channels_is_float32_at_a_kernels_boundary(stage):
    """bfloat16 in: every operand and result of the four ``pallas_call``s
    that is as large as the activation is bfloat16 (float32 only the
    taps, the weight and the few rows of the accumulators), and the backward
    kernels read the op's own inputs and the cotangent, nothing saved."""
    if stage == "conv":
        arrays, _ = _conv_inputs(1, 256, 256, 4, BF16, True)
        fn = lambda *a: CK.causal_conv1d(*a, activation="silu")  # noqa: E731
    else:
        arrays, _ = _norm_inputs(1, 256, 256, BF16, True)
        fn = lambda *a: NK.gated_rms_norm(*a, epsilon=1e-5,      # noqa: E731
                                          num_groups=2)
    grad = jax.grad(lambda *a: fn(*a).astype(F32).sum(), argnums=(0, 1, 2))
    big = arrays[0].size
    calls = [eqn for f in (fn, grad)
             for eqn in _equations(jax.make_jaxpr(f)(*arrays).jaxpr)
             if eqn.primitive.name == "pallas_call"]
    # the forward; and in the gradient the forward again beside the
    # backward, which reads none of its results (so XLA drops it)
    assert [eqn.params["name"][-3:] for eqn in calls] == ["fwd", "fwd", "bwd"]
    assert not set(calls[1].outvars) & set(calls[2].invars)
    for eqn in calls:
        for v in list(eqn.invars) + list(eqn.outvars):
            if v.aval.size >= big:
                assert v.aval.dtype == BF16, eqn.params["name"]
            else:
                assert v.aval.dtype == F32


# -- which calls take the kernels --------------------------------------------

def test_which_calls_take_the_kernels_is_read_off_the_call(monkeypatch):
    """A CPU run, rows that are no whole tile, 96 channels, a group 64
    lanes wide, an ungated norm and a step that spans devices all trace
    the portable path and give its answer; the counters say which path a
    call took."""
    conv_cases = {
        "aligned": (1, 256, 128, 4),
        "rows no whole tile": (1, 200, 128, 4),
        "96 channels": (1, 256, 96, 4),
        "10 taps": (1, 256, 128, 10),
    }
    norm_cases = {
        "aligned": (1, 256, 512, 4),
        "rows no whole tile": (1, 200, 512, 4),
        "a group of 64": (1, 256, 512, 8),
        "a group of 4096": (1, 256, 4096, 1),
    }
    for name, (b, s, c, taps) in conv_cases.items():
        assert CK.supported((b, s, c), taps) == (name == "aligned"), name
    for name, (b, s, d, g) in norm_cases.items():
        assert NK.supported((b, s, d), g) == (name == "aligned"), name
    assert not CK.supported((256, 128), 4)
    assert not NK.supported((256, 512), 4)

    def conv(case):
        b, s, c, taps = conv_cases[case]
        arrays, _ = _conv_inputs(b, s, c, taps, F32, True)
        before = _conv_traced()
        y = F.causal_conv1d(*(pt.to_tensor(np.asarray(a)) for a in arrays),
                            activation="silu")
        np.testing.assert_allclose(
            y.numpy(), _conv1d(*arrays, activation="silu"), atol=2e-6)
        return _took(_conv_traced, before)

    def norm(case, gated=True):
        b, s, d, g = norm_cases[case]
        (y, z, w), _ = _norm_inputs(b, s, d, F32, True)
        before = _norm_traced()
        out = F.rms_norm(pt.to_tensor(np.asarray(y)),
                         pt.to_tensor(np.asarray(w)), 1e-5, g,
                         gate=pt.to_tensor(np.asarray(z)) if gated else None)
        want = _rms_norm(y, *((z, w) if gated else (w,)), epsilon=1e-5,
                         num_groups=g, gated=gated, scaled=True)
        np.testing.assert_allclose(out.numpy(), want, atol=2e-6)
        return _took(_norm_traced, before)

    assert not P.enabled("causal_conv1d")            # this is a CPU
    assert not P.enabled("gated_rms_norm")
    assert conv("aligned") == (0, 1) and norm("aligned") == (0, 1)
    # the TPU's answer steered in: the registry has both kernels on
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    assert P.enabled("causal_conv1d") and P.enabled("gated_rms_norm")
    for case in ("rows no whole tile", "96 channels", "10 taps"):
        assert conv(case) == (0, 1), case
    for case in ("rows no whole tile", "a group of 64", "a group of 4096"):
        assert norm(case) == (0, 1), case
    assert norm("aligned", gated=False) == (0, 0)    # never asks
    with pytest.warns(UserWarning, match="cannot partition a Mosaic"):
        with P.gspmd_trace(4):
            assert not P.enabled("causal_conv1d")
            assert not P.enabled("gated_rms_norm")
            assert conv("aligned") == (0, 1)
            assert norm("aligned") == (0, 1)
    monkeypatch.undo()
    P.configure(causal_conv1d=True, gated_rms_norm=True)    # interpret mode
    try:
        assert conv("aligned") == (1, 0) and norm("aligned") == (1, 0)
        assert conv("96 channels") == (0, 1)    # forced, and not fitting
        assert norm("a group of 64") == (0, 1)
        # a gate or a bias that broadcasts is the portable path's
        (y, z, w), _ = _norm_inputs(1, 256, 512, F32, True)
        before = _norm_traced()
        out = F.rms_norm(pt.to_tensor(np.asarray(y)),
                         pt.to_tensor(np.asarray(w)), 1e-5, 4,
                         gate=pt.to_tensor(np.asarray(z[:, :1])))
        np.testing.assert_allclose(
            out.numpy(), _rms_norm(y, z[:, :1], w, epsilon=1e-5,
                                   num_groups=4, gated=True, scaled=True),
            atol=2e-6)
        assert _took(_norm_traced, before) == (0, 1)
        (x, cw, _), _ = _conv_inputs(1, 256, 128, 4, F32, True)
        before = _conv_traced()
        F.causal_conv1d(pt.to_tensor(np.asarray(x)),
                        pt.to_tensor(np.asarray(cw)),
                        pt.to_tensor(np.ones(1, np.float32)))
        assert _took(_conv_traced, before) == (0, 1)
        assert norm("aligned", gated=False) == (0, 0)
    finally:
        P.configure(causal_conv1d=None, gated_rms_norm=None)
