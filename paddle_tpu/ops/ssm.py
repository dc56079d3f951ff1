"""paddle_tpu.ops.ssm — state-space and short-convolution sequence ops
(Mamba-2; Mamba-1's selective scan; the ``lfm2`` family's gated short
convolution).

No reference counterpart in Paddle Fluid 1.7 (its recurrences are the
LSTM/GRU ops of ops/sequence.py and nn/rnn.py); these are the ops of a
Mamba-2 mixer (Dao & Gu, arXiv:2405.21060): a causal depthwise
convolution, and the selective state-space recurrence in its chunked
"state-space dual" form — all matrix products over chunks of the
sequence, no step-by-step loop, so the MXU does the work and the backward
pass is the products' own.

``F.gated_short_conv`` is here too, the ``lfm2`` family's token mixer
between its two projections: ``y = c * conv(b * u)`` over the one array
``[b | c | u]``. ``_gated_conv`` below is its plain XLA form; on one TPU
it is the second kernel pair of ``ops/pallas/causal_conv1d.py``, which
reads the three thirds in place and writes their gradient as one array
(PERF.md section 6, PR 43); counters ``gated_short_conv.kernel_traced`` /
``gated_short_conv.xla_traced``.

``F.selective_scan`` is Mamba-1's recurrence (Gu & Dao, arXiv:2312.00752):
a transition ``A`` [D, N] that differs for every (channel, state) pair and a
step size for every (token, channel), so the decay between two positions
differs pair by pair and the chunked matrix form above does not exist.
``_selective_scan`` below walks the positions (a ``lax.scan``, chunks of it
recomputed in the backward pass so that a chunk's states are all that is
held); on one TPU the kernel pair of ``ops/pallas/selective_scan.py`` does
the same walk on the vector unit with the state in registers; counters
``selective_scan.kernel_traced`` / ``selective_scan.xla_traced``.

All go through ``dispatch.apply`` (tape autograd, ``jit.to_static``,
``jit.recompute``), and each has two forms of one algorithm, chosen by
what the call shows. The convolution: ``_conv1d`` below, plain XLA, and
on one TPU, where rows and channels fit their tiles, the kernel pair of
``ops/pallas/causal_conv1d.py``, which reads and writes the call's own
dtype in the mixer's ``[B, S, C]`` layout (PERF.md section 6, PR 30);
counters ``causal_conv1d.kernel_traced`` / ``causal_conv1d.xla_traced``.
The scan: ``_ssd`` below, plain XLA, runs anywhere (a CPU, a step
that spans devices, any chunk and width) and is the kernels' oracle; on
one TPU, where the shapes fit their tiles, ``ops/pallas/ssd_scan.py``'s
kernel pair runs it with every chunk x chunk and heads x P x N array in
VMEM (PERF.md section 6, PR 28). Which one a call takes is read off the
call (``ssd_scan`` below), and the counters ``ssd_scan.kernel_traced`` /
``ssd_scan.xla_traced`` say which it was.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..dispatch import apply
from .nn_ops import _pscope

__all__ = ["causal_conv1d", "gated_short_conv", "ssd_scan",
           "selective_scan"]


def _conv1d(x, w, *b, activation):
    """The portable path of ``causal_conv1d`` and the kernels' oracle:
    float32 taps over a padded float32 copy, rounded once."""
    s, taps = x.shape[1], w.shape[1]
    xf = jnp.pad(x.astype(jnp.float32), [(0, 0), (taps - 1, 0), (0, 0)])
    wf = w.astype(jnp.float32)
    y = sum(xf[:, j:j + s] * wf[:, j] for j in range(taps))
    if b:
        y = y + b[0].astype(jnp.float32)
    if activation == "silu":
        y = jax.nn.silu(y)
    return y.astype(x.dtype)


def causal_conv1d(x, weight, bias=None, activation=None, name=None):
    """Depthwise causal convolution along the sequence: ``y[t, c] =
    sum_j weight[c, j] * x[t - (K - 1) + j, c] (+ bias[c])``, positions
    before the start read zero. ``x`` [B, S, C]; ``weight`` [C, K] (tap
    K - 1 multiplies the current position); ``activation`` None or
    "silu"."""
    if activation not in (None, "silu"):
        raise ValueError(f"causal_conv1d: activation {activation!r} is "
                         f"not one of None, 'silu'")
    from .. import monitor
    from . import pallas
    # read off the call, as ssd_scan below: the kernel pair where its
    # tiles fit and the registry has it on, else _conv1d
    kernel = (pallas.enabled("causal_conv1d")
              and pallas.causal_conv1d_mod.supported(
                  tuple(x.shape), int(weight.shape[1]))
              and (bias is None
                   or tuple(bias.shape) == tuple(x.shape[-1:])))
    monitor.counter("causal_conv1d.kernel_traced" if kernel
                    else "causal_conv1d.xla_traced").inc()
    args = (x, weight) if bias is None else (x, weight, bias)
    with _pscope("F.causal_conv1d"):
        return apply(pallas.causal_conv1d_mod.causal_conv1d if kernel
                     else _conv1d, args, dict(activation=activation),
                     name="causal_conv1d")


def _gated_conv(bcx, w):
    """The portable path of ``gated_short_conv`` and the kernels' oracle:
    both gates and the ``K`` shifted products in float32, rounded once."""
    s, taps = bcx.shape[1], w.shape[1]
    b, c, u = jnp.split(bcx.astype(jnp.float32), 3, axis=-1)
    v = jnp.pad(b * u, [(0, 0), (taps - 1, 0), (0, 0)])
    wf = w.astype(jnp.float32)
    z = sum(v[:, j:j + s] * wf[:, j] for j in range(taps))
    return (c * z).astype(bcx.dtype)


def gated_short_conv(bcx, weight, name=None):
    """The double-gated short convolution of the ``lfm2`` family: with
    ``[b | c | u] = bcx`` (thirds of the last axis, in this order), ``y[t]
    = c[t] * sum_j weight[:, j] * (b * u)[t - (K - 1) + j]``; positions
    before a sequence's start read zero, every sequence of the batch its
    own. ``bcx`` [B, S, 3 C]; ``weight`` [C, K] (tap K - 1 multiplies the
    current position, as ``causal_conv1d`` has it); no bias, no
    activation. Returns [B, S, C] in ``bcx``'s dtype."""
    if bcx.shape[-1] != 3 * weight.shape[0]:
        raise ValueError(f"gated_short_conv: bcx {tuple(bcx.shape)} is not "
                         f"three times the {weight.shape[0]} channels of "
                         f"weight {tuple(weight.shape)}")
    from .. import monitor
    from . import pallas
    # read off the call, as causal_conv1d above
    kernel = (pallas.enabled("gated_short_conv")
              and pallas.causal_conv1d_mod.gated_supported(
                  tuple(bcx.shape), int(weight.shape[1])))
    monitor.counter("gated_short_conv.kernel_traced" if kernel
                    else "gated_short_conv.xla_traced").inc()
    with _pscope("F.gated_short_conv"):
        return apply(pallas.causal_conv1d_mod.gated_short_conv if kernel
                     else _gated_conv, (bcx, weight),
                     name="gated_short_conv")


def _chunk_heads(t, k, chunk, g, r):
    """[B, S, G * R, ...] -> [B, K, G, R, L, ...]: heads in front of the
    chunk's positions, so that the two minor dimensions of everything
    below are a chunk's positions or a head's width, never a count of
    heads."""
    t = t.reshape(t.shape[0], k, chunk, g, r, *t.shape[3:])
    return jnp.moveaxis(t, 2, 4)


def _ssd(x, dt, a_log, b, c, d_skip, dt_bias, *, chunk, dot_dtype):
    """The recurrence ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``,
    ``y_t = H_t C_t + D x_t`` per head, evaluated by chunks: inside a
    chunk the masked decay matrix ``L[t, s] = exp(sum_{s < r <= t} dt_r
    A)`` on ``(C_t . B_s) dt_s x_s``; between chunks the carried state.
    Decays and their cumulative sums in float32; the products take
    ``dot_dtype`` operands and accumulate in float32."""
    f32 = jnp.float32
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))     # [B,S,H]
    a = -jnp.exp(a_log.astype(f32))
    xdt = x.astype(f32) * dt[..., None]
    pad = -s % chunk
    if pad:     # dt = 0 there: the state stands still, nothing reads them
        xdt, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad)]
                                 + [(0, 0)] * (t.ndim - 2))
                         for t in (xdt, dt, b, c))
    k = (s + pad) // chunk
    xc = _chunk_heads(xdt.astype(dot_dtype), k, chunk, g, r)   # [B,K,G,R,L,P]
    bc = jnp.moveaxis(b.astype(dot_dtype).reshape(bsz, k, chunk, g, n), 2, 3)
    cc = jnp.moveaxis(c.astype(dot_dtype).reshape(bsz, k, chunk, g, n), 2, 3)
    cum = jnp.cumsum(_chunk_heads(dt * a, k, chunk, g, r), -1)  # [B,K,G,R,L]

    # inside a chunk. A chunk's sums stay small (|dt A| <= ~2 a step), so
    # the difference of two cumulative sums loses nothing that matters
    seg = cum[..., :, None] - cum[..., None, :]                 # [.., t, s]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, seg, 0.0)), 0.0)
    scores = jnp.einsum("bkgtn,bkgsn->bkgts", cc, bc,
                        preferred_element_type=f32)
    y = jnp.einsum("bkgrts,bkgrsp->bkgrtp",
                   (scores[:, :, :, None] * decay).astype(dot_dtype), xc,
                   preferred_element_type=f32)

    # what each chunk leaves behind, decayed to the chunk's end
    left = jnp.exp(cum[..., -1:] - cum)                          # [B,K,G,R,L]
    states = jnp.einsum(
        "bkgsn,bkgrsp->bkgrpn", bc,
        (xc.astype(f32) * left[..., None]).astype(dot_dtype),
        preferred_element_type=f32)                              # [B,K,G,R,P,N]

    # between chunks: the state that enters chunk k is the sum over j < k
    # of exp(sum_{j < i < k} total_i) states_j. The K x K sums are taken
    # term by term (masked cumulative sum), not as differences of sums
    # that grow with the sequence
    total = jnp.moveaxis(cum[..., -1], 1, -1)                    # [B,G,R,K]
    rows = jnp.arange(k)[:, None]
    cols = jnp.arange(k)[None, :]
    seg_k = jnp.cumsum(jnp.where(rows > cols, total[..., :, None], 0.0), -2)
    carry = jnp.where(rows >= cols, jnp.exp(jnp.where(rows >= cols, seg_k,
                                                       0.0)), 0.0)
    after = jnp.einsum("bgrkj,bjgrpn->bkgrpn", carry, states,
                       precision="highest")          # state after chunk k
    entering = jnp.pad(after[:, :-1], [(0, 0), (1, 0)] + [(0, 0)] * 4)
    y = y + jnp.einsum("bkgtn,bkgrpn->bkgrtp", cc, entering.astype(dot_dtype),
                       preferred_element_type=f32) * jnp.exp(cum)[..., None]

    y = jnp.moveaxis(y, 4, 2).reshape(bsz, k * chunk, h, p)[:, :s]
    y = y + d_skip.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype)


def ssd_scan(x, dt, A_log, B, C, D, dt_bias, chunk_size=128, name=None):
    """Mamba-2's selective state-space recurrence, by chunks.

    ``x`` [B, S, H, P] (heads x head width); ``dt`` [B, S, H], the raw
    step sizes (``softplus(dt + dt_bias)`` is taken here, in float32);
    ``A_log``, ``D``, ``dt_bias`` [H] (``A = -exp(A_log)``); ``B``, ``C``
    [B, S, G, N] (head h reads group ``h // (H / G)``). Returns ``y`` [B,
    S, H, P] in ``x``'s dtype. Any sequence length; under
    ``amp.auto_cast`` the matrix products take the compute dtype's
    operands, everything else stays float32."""
    from .. import amp, monitor
    from . import pallas
    dot_dtype = amp.compute_dtype() if amp.is_enabled() else None
    # read off the call, not set by a user: the kernels where their tiles
    # fit the shapes and the registry has them on (a TPU, the step on one
    # device), else the portable path
    kernel = (pallas.enabled("ssd_scan") and pallas.ssd_scan_mod.supported(
        tuple(x.shape), tuple(B.shape), int(chunk_size)))
    monitor.counter("ssd_scan.kernel_traced" if kernel
                    else "ssd_scan.xla_traced").inc()
    scan = pallas.ssd_scan_mod.ssd_scan if kernel else _ssd

    def impl(x, dt, a_log, b, c, d_skip, dt_bias, *, chunk):
        return scan(x, dt, a_log, b, c, d_skip, dt_bias, chunk=chunk,
                    dot_dtype=dot_dtype or jnp.result_type(x))

    with _pscope("F.ssd_scan"):
        return apply(impl, (x, dt, A_log, B, C, D, dt_bias),
                     dict(chunk=int(chunk_size)), name="ssd_scan")


def _selective_scan(x, dt, a, b, c, d_skip, *, chunk):
    """The portable path of ``selective_scan`` and the kernels' oracle:
    ``H_t = exp(dt_t A) * H_{t-1} + (dt_t x_t) B_t^T``, ``y_t = H_t C_t + D
    x_t`` position by position in float32, a chunk's walk recomputed in
    the backward pass (what is kept is the state that enters each chunk).
    ``dt`` holds the step sizes (softplus taken), ``a`` is negative."""
    f32 = jnp.float32
    bsz, s, d = x.shape
    pad = -s % chunk
    xf, dt, b, c = (t.astype(f32) for t in (x, dt, b, c))
    if pad:     # dt = 0 there: the state stands still, nothing reads them
        xf, dt, b, c = (jnp.pad(t, [(0, 0), (0, pad), (0, 0)])
                        for t in (xf, dt, b, c))
    a, d_skip = a.astype(f32), d_skip.astype(f32)

    def position(h, at):
        x_t, dt_t, b_t, c_t = at                    # [B, D] x2, [B, N] x2
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], -1) + d_skip * x_t

    @jax.checkpoint
    def chunk_of(h, rows):
        return jax.lax.scan(position, h, rows)

    rows = tuple(jnp.moveaxis(t, 1, 0).reshape(
        (s + pad) // chunk, chunk, bsz, t.shape[-1]) for t in (xf, dt, b, c))
    _, y = jax.lax.scan(chunk_of, jnp.zeros((bsz, d, a.shape[1]), f32), rows)
    return jnp.moveaxis(y.reshape(s + pad, bsz, d), 0, 1)[:, :s]


def selective_scan(x, dt, A_log, B, C, D, dt_bias=None, z=None,
                   chunk_size=None, force=False, name=None):
    """Mamba-1's selective state-space recurrence, position by position.

    ``x`` [B, S, D] (channels); ``dt`` [B, S, D], the raw step sizes
    (``softplus(dt + dt_bias)`` is taken here, in float32); ``A_log`` [D,
    N] (``A = -exp(A_log)``: a transition for every (channel, state)
    pair); ``B``, ``C`` [B, S, N], shared by the channels; ``D``,
    ``dt_bias`` [D]. Returns ``y`` [B, S, D] in ``x``'s dtype, or with a
    gate ``z`` [B, S, D] the pair ``(y * silu(z), y)``: the gated result
    and the UN-GATED one, which a decoder-hybrid-decoder hands on as its
    memory. Any sequence length. State, step sizes, exponentials and the
    gate are float32 whatever ``x`` is. ``force`` takes the kernels off a
    TPU too (interpret mode: the kernel tests)."""
    from .. import monitor
    from . import pallas
    mod = pallas.selective_scan_mod
    chunk = int(chunk_size or mod.CHUNK)
    kernel = (force or pallas.enabled("selective_scan")) and mod.supported(
        tuple(x.shape), int(A_log.shape[1]), chunk)
    monitor.counter("selective_scan.kernel_traced" if kernel
                    else "selective_scan.xla_traced").inc()
    scan = mod.selective_scan if kernel else _selective_scan
    extras = {k: t for k, t in (("dt_bias", dt_bias), ("z", z))
              if t is not None}

    def impl(x, dt, a_log, b, c, d_skip, *rest):
        f32 = jnp.float32
        given = dict(zip(extras, rest))
        dt = dt.astype(f32)
        if "dt_bias" in given:
            dt = dt + given["dt_bias"].astype(f32)
        y = scan(x, jax.nn.softplus(dt), -jnp.exp(a_log.astype(f32)), b, c,
                 d_skip, chunk=chunk)
        if "z" not in given:
            return y.astype(x.dtype)
        return ((y * jax.nn.silu(given["z"].astype(f32))).astype(x.dtype),
                y.astype(x.dtype))

    with _pscope("F.selective_scan"):
        return apply(impl, (x, dt, A_log, B, C, D, *extras.values()),
                     name="selective_scan")
