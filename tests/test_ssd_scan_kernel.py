"""The Pallas kernel pair behind ``F.ssd_scan`` (ops/pallas/ssd_scan.py), in
interpret mode on the CPU, against the step-by-step recurrence of the
benchmark's plain reference: forward and all seven gradients; which calls
take the kernels and which keep ``ops/ssm.py: _ssd``; and that decays,
sums and accumulations stay float32 whatever the products' dtype.
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
from paddle_tpu import amp, monitor                         # noqa: E402
from paddle_tpu.nn import functional as F                   # noqa: E402
from paddle_tpu.ops import pallas as P                      # noqa: E402
from paddle_tpu.ops.pallas import ssd_scan as K             # noqa: E402
from benchmark.reference import nemotron_h as R             # noqa: E402

ORDER = ("x", "dt", "a_log", "b", "c", "d", "dt_bias")
CHUNK = 128


def _inputs(batch, seq, groups, per_group, width, state=128, seed=1):
    heads = groups * per_group
    k = jax.random.split(jax.random.key(seed), 7)
    return dict(
        x=jax.random.normal(k[0], (batch, seq, heads, width)),
        dt=jax.random.normal(k[1], (batch, seq, heads)),
        a_log=jnp.log(jax.random.uniform(k[2], (heads,), minval=1.0,
                                         maxval=16.0)),
        b=jax.random.normal(k[3], (batch, seq, groups, state)),
        c=jax.random.normal(k[4], (batch, seq, groups, state)),
        d=jax.random.normal(k[5], (heads,)),
        dt_bias=jax.random.normal(k[6], (heads,)) - 2.0)


def _step_by_step(x, dt, a_log, b, c, d, dt_bias):
    r = x.shape[2] // b.shape[2]
    one = lambda x, dt, b, c: R.ssm_step_by_step(       # noqa: E731
        x, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
        jnp.repeat(b, r, 1), jnp.repeat(c, r, 1), d)
    return jax.vmap(one)(x, dt, b, c)


def _traced():
    seen = monitor.snapshot("ssd_scan")
    return (int(seen.get("ssd_scan.kernel_traced", 0)),
            int(seen.get("ssd_scan.xla_traced", 0)))


@pytest.fixture()
def kernel_forced():
    P.configure(ssd_scan=True)
    try:
        yield
    finally:
        P.configure(ssd_scan=None)


# chunks x heads a group (R = 1 is one 128-wide head, R = 8 eight 64-wide
# heads: two heads a lane tile) x batch x the products' dtype; two groups
# once, so that a group's lanes of x, B and C are found by the index maps
@pytest.mark.parametrize("chunks,per_group,batch,groups,autocast", [
    (1, 1, 1, 1, False),
    (3, 8, 2, 1, False),
    (3, 1, 2, 2, False),
    (1, 8, 1, 2, True),
    (3, 8, 1, 1, True),
    (3, 1, 2, 1, True),
], ids=["1chunk-R1-b1-f32", "3chunks-R8-b2-f32", "3chunks-R1-b2-G2-f32",
        "1chunk-R8-b1-G2-bf16", "3chunks-R8-b1-bf16", "3chunks-R1-b2-bf16"])
def test_kernels_equal_the_recurrence_forward_and_all_seven_gradients(
        kernel_forced, chunks, per_group, batch, groups, autocast):
    width = 128 if per_group == 1 else 64
    t = _inputs(batch, chunks * CHUNK, groups, per_group, width)
    tensors = {k: pt.to_tensor(np.asarray(v)) for k, v in t.items()}
    for v in tensors.values():
        v.stop_gradient = False
    before = _traced()
    if autocast:
        with amp.auto_cast(dtype="bfloat16"):
            y = F.ssd_scan(*(tensors[k] for k in ORDER), chunk_size=CHUNK)
    else:
        y = F.ssd_scan(*(tensors[k] for k in ORDER), chunk_size=CHUNK)
    after = _traced()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 0)
    assert y.dtype == jnp.float32            # x's dtype, not the products'

    want = _step_by_step(*(t[k] for k in ORDER))
    # float32 throughout, or bfloat16 operands of products that accumulate
    # in float32 (the portable path's own distance, tests/test_ssm_ops)
    tol = 0.02 if autocast else 2e-5
    assert np.abs(y.numpy() - want).max() < tol * np.abs(want).max()

    probe = jax.random.normal(jax.random.key(9), want.shape)
    (y * pt.to_tensor(np.asarray(probe))).sum().backward()
    grads = jax.grad(lambda *a: jnp.sum(_step_by_step(*a) * probe),
                     argnums=tuple(range(7)))(*(t[k] for k in ORDER))
    for name, g in zip(ORDER, grads):
        got, g = np.asarray(tensors[name]._grad), np.asarray(g)
        assert got.shape == g.shape
        assert np.abs(got - g).max() / (np.abs(g).max() + 1e-12) < \
            (0.03 if autocast else 1e-4), name


def test_kernels_give_the_portable_paths_numbers(kernel_forced):
    """Against ``_ssd`` itself, same dtypes: the kernels carry the state
    chunk to chunk where ``_ssd`` sums K x K decays, nothing else differs."""
    from paddle_tpu.ops.ssm import _ssd
    t = _inputs(1, 2 * CHUNK, 2, 2, 64, seed=4)
    args = [t[k] for k in ORDER]
    for dot_dtype, tol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-3)):
        want = _ssd(*args, chunk=CHUNK, dot_dtype=dot_dtype)
        got = K.ssd_scan(*args, chunk=CHUNK, dot_dtype=dot_dtype)
        assert np.abs(got - want).max() < tol * np.abs(want).max()


def test_kernels_at_a_chunk_of_256_and_a_head_256_wide(kernel_forced):
    """The tiles follow the chunk and the head's width: a chunk of two
    lane tiles, a head of two, forward and gradients against ``_ssd``."""
    from paddle_tpu.ops.ssm import _ssd
    t = _inputs(1, 512, 1, 1, 256, seed=6)
    args = [t[k] for k in ORDER]
    assert K.supported(t["x"].shape, t["b"].shape, 256)
    grads = []
    for scan in (_ssd, K.ssd_scan):
        f = lambda *a: scan(*a, chunk=256,                 # noqa: E731
                            dot_dtype=jnp.float32)
        grads.append((f(*args), jax.grad(lambda *a: jnp.sum(f(*a) ** 2),
                                         argnums=tuple(range(7)))(*args)))
    (want, want_g), (got, got_g) = grads
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    for name, a, b in zip(ORDER, want_g, got_g):
        assert np.abs(a - b).max() < 2e-4 * np.abs(a).max(), name


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (the kernels' bodies, custom_vjp calls, pjit)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else (value,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def test_decays_sums_and_accumulation_stay_float32_under_bf16_products():
    """Forward and backward with bfloat16 products: every ``exp``, every
    sum and every product's result is float32; bfloat16 appears only as a
    product's operand. (``test_scan_keeps_decays_in_float32_under_autocast``
    of tests/test_ssm_ops.py, for the kernels.)"""
    t = _inputs(1, 2 * CHUNK, 1, 8, 64)
    args = [t[k] for k in ORDER]
    f = lambda *a: K.ssd_scan(*a, chunk=CHUNK,            # noqa: E731
                              dot_dtype=jnp.bfloat16)
    grad = jax.grad(lambda *a: f(*a).sum(), argnums=tuple(range(7)))
    seen = {"exp": 0, "dot_general": 0, "reduce_sum": 0, "pallas_call": 0}
    for fn in (f, grad):
        for eqn in _equations(jax.make_jaxpr(fn)(*args).jaxpr):
            name = eqn.primitive.name
            if name in seen:
                seen[name] += 1
            if name in ("exp", "reduce_sum", "cumsum", "dot_general",
                        "softplus", "logistic", "log1p"):
                assert all(v.aval.dtype == jnp.float32
                           for v in eqn.outvars), eqn
            if name == "dot_general" and any(
                    v.aval.dtype == jnp.bfloat16 for v in eqn.invars):
                assert eqn.params["preferred_element_type"] == jnp.float32
    assert seen["pallas_call"] == 3      # forward, forward + residual, backward
    assert seen["exp"] >= 8 and seen["dot_general"] >= 20


def test_which_calls_take_the_kernels_is_read_off_the_call(monkeypatch):
    """A CPU run, ``chunk_size=8``, a width the tiles do not fit and a
    step that spans devices all trace ``_ssd``, give the recurrence's
    answer, and the two counters say which path a call took."""
    aligned = _inputs(1, CHUNK, 1, 2, 64)
    cases = {
        "aligned": (aligned, CHUNK),
        "chunk of 8": (aligned, 8),
        "a tail under one chunk": (_inputs(1, CHUNK + 24, 1, 2, 64), CHUNK),
        "head width 32": (_inputs(1, CHUNK, 1, 4, 32), CHUNK),
        "state 64": (_inputs(1, CHUNK, 2, 2, 64, state=64), CHUNK),
        "one 64-wide head a group": (_inputs(1, CHUNK, 2, 1, 64), CHUNK),
    }
    for name, (t, chunk) in cases.items():
        assert K.supported(t["x"].shape, t["b"].shape, chunk) == \
            (name == "aligned"), name

    def run(t, chunk):
        before = _traced()
        y = F.ssd_scan(*(pt.to_tensor(np.asarray(t[k])) for k in ORDER),
                       chunk_size=chunk)
        after = _traced()
        want = _step_by_step(*(t[k] for k in ORDER))
        np.testing.assert_allclose(y.numpy(), want, atol=2e-4, rtol=2e-4)
        return after[0] - before[0], after[1] - before[1]

    assert not P.enabled("ssd_scan")                 # this is a CPU
    assert run(*cases["aligned"]) == (0, 1)
    # the TPU's answer steered in: the registry has the kernel on
    monkeypatch.setattr(P, "interpret_mode", lambda: False)
    assert P.enabled("ssd_scan")
    for name in ("chunk of 8", "a tail under one chunk", "head width 32"):
        assert run(*cases[name]) == (0, 1), name
    with pytest.warns(UserWarning, match="cannot partition a Mosaic"):
        with P.gspmd_trace(4):
            assert not P.enabled("ssd_scan")
            assert run(*cases["aligned"]) == (0, 1)
    monkeypatch.undo()
    P.configure(ssd_scan=True)                       # interpret mode
    try:
        assert run(*cases["aligned"]) == (1, 0)
        assert run(*cases["chunk of 8"]) == (0, 1)   # forced, and not fitting
    finally:
        P.configure(ssd_scan=None)
    with pytest.raises(ValueError, match="unknown pallas kernel"):
        P.configure(ssd_scann=True)
