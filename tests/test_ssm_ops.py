"""The state-space mixer's ops on their portable paths, on the CPU:
``F.selective_scan`` (Mamba-1's recurrence: its portable path and its
kernel pair, ``force``d, in interpret mode) against the time-step
recurrence of benchmark/reference/phi4_flash.py; ``F.ssd_scan`` (the
chunked Mamba-2 scan) against the step-by-step
recurrence of the benchmark's plain reference (benchmark/reference/
nemotron_h.py), forward and every gradient, over whole chunks and a padded
tail; ``F.causal_conv1d`` against a plain sum; ``nn.RMSNorm``, grouped and
gated, against the reference's. No model is built here
(tests/test_nemotron_h.py has the model; the kernel pairs of these ops are
in tests/test_ssd_scan_kernel.py and tests/test_mixer_stage_kernels.py)."""
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
from paddle_tpu import amp, nn                              # noqa: E402
from paddle_tpu.nn import functional as F                   # noqa: E402
from benchmark.reference import nemotron_h as R             # noqa: E402
from benchmark.reference import phi4_flash as R1            # noqa: E402


# -- the state-space scan ---------------------------------------------------

def _scan_inputs(seq, heads=4, width=8, groups=2, state=16, seed=1):
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (2, seq, heads, width)),
        dt=jax.random.normal(k[1], (2, seq, heads)),
        a_log=jnp.log(jax.random.uniform(k[2], (heads,), minval=1.0,
                                         maxval=16.0)),
        b=jax.random.normal(k[3], (2, seq, groups, state)),
        c=jax.random.normal(k[4], (2, seq, groups, state)),
        d=jax.random.normal(k[5], (heads,)),
        dt_bias=jax.random.normal(k[6], (heads,)) - 2.0)


def _step_by_step(x, dt, a_log, b, c, d, dt_bias):
    r = x.shape[2] // b.shape[2]
    one = lambda x, dt, b, c: R.ssm_step_by_step(       # noqa: E731
        x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
        jnp.repeat(b, r, 1), jnp.repeat(c, r, 1), d)
    return jax.vmap(one)(x, dt, b, c)


# 16, 32: whole chunks; 21, 5: a padded tail, and fewer positions than one
@pytest.mark.parametrize("seq", [16, 32, 21, 5])
def test_chunked_scan_equals_the_recurrence_forward_and_gradient(seq):
    t = _scan_inputs(seq)
    order = ("x", "dt", "a_log", "b", "c", "d", "dt_bias")
    tensors = {k: pt.to_tensor(np.asarray(v)) for k, v in t.items()}
    for v in tensors.values():
        v.stop_gradient = False
    y = F.ssd_scan(tensors["x"], tensors["dt"], tensors["a_log"],
                   tensors["b"], tensors["c"], tensors["d"],
                   tensors["dt_bias"], chunk_size=8)
    want = _step_by_step(*(t[k] for k in order))
    np.testing.assert_allclose(y.numpy(), want, atol=2e-4, rtol=2e-4)

    probe = jax.random.normal(jax.random.key(9), want.shape)
    (y * pt.to_tensor(np.asarray(probe))).sum().backward()
    grads = jax.grad(lambda *a: jnp.sum(_step_by_step(*a) * probe),
                     argnums=tuple(range(7)))(*(t[k] for k in order))
    for name, g in zip(order, grads):
        got = np.asarray(tensors[name]._grad)
        scale = np.abs(np.asarray(g)).max() + 1e-12
        assert np.abs(got - np.asarray(g)).max() / scale < 5e-4, name


def test_scan_keeps_decays_in_float32_under_autocast():
    t = _scan_inputs(24)
    args = [pt.to_tensor(np.asarray(t[k]))
            for k in ("x", "dt", "a_log", "b", "c", "d", "dt_bias")]
    want = F.ssd_scan(*args, chunk_size=8).numpy()
    with amp.auto_cast(dtype="bfloat16"):
        got = F.ssd_scan(*args, chunk_size=8)
    assert got.dtype == jnp.float32          # x's dtype, not the products'
    assert np.abs(got.numpy() - want).max() < 0.05 * np.abs(want).max()


def test_causal_conv1d_is_causal_and_matches_a_plain_sum():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 9, 6)).astype(np.float32)
    w = rng.normal(size=(6, 4)).astype(np.float32)
    b = rng.normal(size=(6,)).astype(np.float32)
    y = F.causal_conv1d(pt.to_tensor(x), pt.to_tensor(w), pt.to_tensor(b),
                        activation="silu").numpy()
    pad = np.concatenate([np.zeros((2, 3, 6), np.float32), x], 1)
    want = sum(pad[:, j:j + 9] * w[:, j] for j in range(4)) + b
    want = want / (1.0 + np.exp(-want))
    np.testing.assert_allclose(y, want, atol=1e-5)
    x2 = x.copy()
    x2[:, 5:] += 1.0                         # the future moves nothing past
    y2 = F.causal_conv1d(pt.to_tensor(x2), pt.to_tensor(w), pt.to_tensor(b),
                         activation="silu").numpy()
    np.testing.assert_array_equal(y[:, :5], y2[:, :5])
    with pytest.raises(ValueError, match="activation"):
        F.causal_conv1d(pt.to_tensor(x), pt.to_tensor(w), activation="gelu")


# -- RMS norm ---------------------------------------------------------------

@pytest.mark.parametrize("groups,gated", [(1, False), (4, False), (4, True)])
def test_rms_norm_matches_the_reference(groups, gated):
    rng = np.random.default_rng(3)
    x = rng.normal(size=(3, 5, 32)).astype(np.float32)
    g = rng.normal(size=(3, 5, 32)).astype(np.float32)
    w = rng.normal(size=(32,)).astype(np.float32)
    layer = nn.RMSNorm(32, epsilon=1e-5, num_groups=groups)
    layer.weight.set_value(w)
    got = layer(pt.to_tensor(x), gate=pt.to_tensor(g) if gated else None)
    inp = x * np.asarray(jax.nn.silu(g)) if gated else x
    want = R._rms_norm(jnp.asarray(inp), jnp.asarray(w), 1e-5, groups)
    np.testing.assert_allclose(got.numpy(), want, atol=1e-5)
    with amp.auto_cast(dtype="bfloat16"):
        half = layer(pt.to_tensor(x).astype("bfloat16"))
    assert half.dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="groups"):
        nn.RMSNorm(30, num_groups=4)


# -- Mamba-1's selective scan ------------------------------------------------

_SEL = ("x", "dt", "a_log", "b", "c", "d", "dt_bias", "z")


def _selective_inputs(seq, channels=1024, state=4, seed=2):
    k = jax.random.split(jax.random.key(seed), 8)
    return dict(
        x=jax.random.normal(k[0], (2, seq, channels)),
        dt=jax.random.normal(k[1], (2, seq, channels)),
        a_log=jnp.log(jax.random.uniform(k[2], (channels, state),
                                         minval=1.0, maxval=16.0)),
        b=jax.random.normal(k[3], (2, seq, state)),
        c=jax.random.normal(k[4], (2, seq, state)),
        d=jax.random.normal(k[5], (channels,)),
        dt_bias=jax.random.normal(k[6], (channels,)) - 2.0,
        z=jax.random.normal(k[7], (2, seq, channels)))


def _time_steps(x, dt, a_log, b, c, d, dt_bias, z):
    """(gated, un-gated) by the reference's time-step recurrence."""
    y = jax.vmap(lambda x, dt, b, c: R1.selective_scan(
        x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log), b, c, d))(
            x, dt, b, c)
    return y * jax.nn.silu(z), y


# 16: whole chunks; 21: a length that is no multiple of the chunk (the
# portable path pads it; the kernels do not take it and the op falls back)
@pytest.mark.parametrize("seq,force,path", [
    (16, False, "xla"), (21, False, "xla"), (16, True, "kernel"),
    (21, True, "xla")], ids=["portable", "portable-tail", "kernel",
                             "kernel-asked-tail"])
def test_selective_scan_equals_the_time_step_recurrence(seq, force, path):
    """Forward (the gated result AND the un-gated one that is handed on)
    and each gradient, of both results at once."""
    from paddle_tpu import monitor
    t = _selective_inputs(seq)
    tensors = {k: pt.to_tensor(np.asarray(v)) for k, v in t.items()}
    for v in tensors.values():
        v.stop_gradient = False
    before = monitor.snapshot("selective_scan")
    gated, y = F.selective_scan(
        tensors["x"], tensors["dt"], tensors["a_log"], tensors["b"],
        tensors["c"], tensors["d"], dt_bias=tensors["dt_bias"],
        z=tensors["z"], chunk_size=8, force=force)
    took = {k: v - before.get(k, 0)
            for k, v in monitor.snapshot("selective_scan").items()}
    assert took.get(f"selective_scan.{path}_traced") == 1, took
    want_gated, want = _time_steps(*(t[k] for k in _SEL))
    np.testing.assert_allclose(y.numpy(), want, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(gated.numpy(), want_gated, atol=2e-5,
                               rtol=2e-5)
    p1, p2 = (jax.random.normal(jax.random.key(9 + i), want.shape)
              for i in (0, 1))
    ((gated * pt.to_tensor(np.asarray(p1))).sum()
     + (y * pt.to_tensor(np.asarray(p2))).sum()).backward()

    def loss(*a):
        g, u = _time_steps(*a)
        return jnp.sum(g * p1) + jnp.sum(u * p2)

    grads = jax.grad(loss, argnums=tuple(range(8)))(*(t[k] for k in _SEL))
    for name, g in zip(_SEL, grads):
        got = np.asarray(tensors[name]._grad)
        scale = np.abs(np.asarray(g)).max() + 1e-12
        assert np.abs(got - np.asarray(g)).max() / scale < 2e-5, name


def test_selective_scan_without_a_gate_and_in_float32_under_autocast():
    t = _selective_inputs(16, channels=64)
    args = [pt.to_tensor(np.asarray(t[k])) for k in _SEL[:6]]
    want = _time_steps(*(t[k] for k in _SEL))[1]
    y = F.selective_scan(*args, dt_bias=pt.to_tensor(np.asarray(t["dt_bias"])))
    np.testing.assert_allclose(y.numpy(), want, atol=2e-5, rtol=2e-5)
    # state, step sizes and exponentials stay float32 whatever x is: a
    # bfloat16 x costs its own rounding and no more
    half = [a.astype("bfloat16") if i in (0, 3, 4) else a
            for i, a in enumerate(args)]
    with amp.auto_cast(dtype="bfloat16"):
        got = F.selective_scan(
            *half, dt_bias=pt.to_tensor(np.asarray(t["dt_bias"])))
    assert got.dtype == jnp.bfloat16
    rounded = _time_steps(*(
        t[k].astype(jnp.bfloat16).astype(jnp.float32)
        if k in ("x", "b", "c") else t[k] for k in _SEL))[1]
    assert np.abs(got.numpy().astype(np.float32) - rounded).max() \
        < 0.01 * np.abs(rounded).max()


def test_a_bfloat16_scan_state_is_seen_by_the_recurrences_tolerance():
    """What the tolerance above is for: the state rounded to bfloat16
    after every position is 30 x outside it."""
    t = _selective_inputs(64, channels=64)
    a = [t[k] for k in _SEL]
    want = _time_steps(*a)[1]
    low = jax.vmap(lambda x, dt, b, c: R1.selective_scan(
        x, jax.nn.softplus(dt + t["dt_bias"]), -jnp.exp(t["a_log"]), b, c,
        t["d"], jnp.bfloat16))(t["x"], t["dt"], t["b"], t["c"])
    assert np.abs(np.asarray(low - want)).max() > 30 * 2e-5 * (
        1 + np.abs(np.asarray(want)).max())
