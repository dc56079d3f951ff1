"""The state-space mixer's ops on their portable paths, on the CPU:
``F.ssd_scan`` (the chunked Mamba-2 scan) against the step-by-step
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
