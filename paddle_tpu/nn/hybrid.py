"""Sequence mixers of hybrid and latent-attention language models: Mamba-1
and Mamba-2 mixers, a gated short convolution, grouped-query, sparse,
differential and multi-head latent attention, a gated memory unit.

No reference counterpart in Paddle Fluid 1.7. All map ``[B, S, hidden]``
to ``[B, S, hidden]``, no dropout, no cache: the training path; NO BIAS but
in the phi4flash layers at the end of the file (PERF.md section 7: serving).
"""
from __future__ import annotations

import math

import numpy as np

from .layer import Layer
from .layers import Linear, RMSNorm
from .. import initializer as I
from ..ops import nn_ops as F
from ..ops import ssm as S

__all__ = ["Mamba2Mixer", "GatedShortConv", "GroupedQueryAttention",
           "SparseGroupedQueryAttention", "MultiHeadLatentAttention"]


class Mamba2Mixer(Layer):
    """Mamba-2 mixer (Dao & Gu, arXiv:2405.21060) as the ``nemotron_h``
    and ``mamba2`` model codes lay it out: ``[z | xBC | dt] = u W_in``; a
    causal depthwise convolution of ``conv_kernel`` taps and SiLU over
    ``xBC = [x | B | C]``; the selective recurrence (``F.ssd_scan``) with
    ``num_heads`` heads of ``head_dim``, ``n_groups`` groups of ``B`` and
    ``C`` of ``state_size`` (head h reads group ``h // (num_heads /
    n_groups)``); the gate ``silu(z)`` goes on before a grouped RMS norm;
    then ``W_out``. The inner width is ``num_heads * head_dim``."""

    def __init__(self, hidden_size, num_heads, head_dim, state_size,
                 n_groups=1, conv_kernel=4, chunk_size=128, epsilon=1e-5,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4):
        super().__init__()
        if num_heads % n_groups:
            raise ValueError(f"Mamba2Mixer: {num_heads} heads do not split "
                             f"into {n_groups} groups")
        self.num_heads, self.head_dim = num_heads, head_dim
        self.state_size, self.n_groups = state_size, n_groups
        self.chunk_size = chunk_size
        self.inner = num_heads * head_dim
        self.conv_dim = self.inner + 2 * n_groups * state_size
        self.in_proj = Linear(hidden_size,
                              self.inner + self.conv_dim + num_heads,
                              bias_attr=False)
        tap = 1.0 / math.sqrt(conv_kernel)
        self.conv_weight = self.create_parameter(
            (self.conv_dim, conv_kernel),
            default_initializer=I.Uniform(-tap, tap))
        self.conv_bias = self.create_parameter(
            (self.conv_dim,), default_initializer=I.Uniform(-tap, tap))
        # the step sizes start log-uniform in [min, max] (softplus^-1 of
        # them), A = -exp(A_log) uniform in -[1, 16], D = 1
        rng = np.random.default_rng(0)
        dt = np.maximum(np.exp(rng.uniform(math.log(time_step_min),
                                           math.log(time_step_max),
                                           num_heads)), time_step_floor)
        self.dt_bias = self.create_parameter(
            (num_heads,), default_initializer=I.Assign(
                (dt + np.log(-np.expm1(-dt))).astype("float32")))
        self.A_log = self.create_parameter(
            (num_heads,), default_initializer=I.Assign(
                np.log(rng.uniform(1.0, 16.0, num_heads)).astype("float32")))
        self.D = self.create_parameter(
            (num_heads,), default_initializer=I.Constant(1.0))
        self.norm = RMSNorm(self.inner, epsilon, num_groups=n_groups)
        self.out_proj = Linear(self.inner, hidden_size, bias_attr=False)

    def forward(self, u):
        b, s = u.shape[0], u.shape[1]
        h, p, g, n = (self.num_heads, self.head_dim, self.n_groups,
                      self.state_size)
        zxbcdt = self.in_proj(u)
        z = zxbcdt[:, :, :self.inner]
        xbc = zxbcdt[:, :, self.inner:self.inner + self.conv_dim]
        dt = zxbcdt[:, :, self.inner + self.conv_dim:]
        xbc = S.causal_conv1d(xbc, self.conv_weight, self.conv_bias,
                              activation="silu")
        x = xbc[:, :, :self.inner].reshape([b, s, h, p])
        bb = xbc[:, :, self.inner:self.inner + g * n].reshape([b, s, g, n])
        cc = xbc[:, :, self.inner + g * n:].reshape([b, s, g, n])
        y = S.ssd_scan(x, dt, self.A_log, bb, cc, self.D, self.dt_bias,
                       chunk_size=self.chunk_size)
        y = self.norm(y.reshape([b, s, self.inner]), gate=z)
        return self.out_proj(y)


class GatedShortConv(Layer):
    """The gated short convolution of the ``lfm2`` family (the ``lfm2`` /
    ``lfm2_moe`` model codes' ``ShortConv``): ``[b | c | x] = u W_in``, a
    causal depthwise convolution of ``taps`` taps over ``b * x`` with no
    bias and no activation, the gate ``c`` on its result, then ``W_out``
    (``F.gated_short_conv`` between the two projections). The inner width
    is the hidden size; no bias anywhere."""

    def __init__(self, hidden_size, taps=3):
        super().__init__()
        self.in_proj = Linear(hidden_size, 3 * hidden_size, bias_attr=False)
        tap = 1.0 / math.sqrt(taps)
        self.conv_weight = self.create_parameter(
            (hidden_size, taps), default_initializer=I.Uniform(-tap, tap))
        self.out_proj = Linear(hidden_size, hidden_size, bias_attr=False)

    def forward(self, u):
        return self.out_proj(S.gated_short_conv(self.in_proj(u),
                                                self.conv_weight))


class GroupedQueryAttention(Layer):
    """Self-attention with fewer key/value heads than query heads
    (Ainslie et al., arXiv:2305.13245): KV head j serves the query heads
    ``[j r, (j + 1) r)``, ``r = num_heads / num_kv_heads``. No bias. As it
    stands (``nemotron_h``) it has no position embedding and no norm of
    its own. ``qk_norm_epsilon`` adds an RMS norm over each query and each
    key head (``q_norm`` / ``k_norm``, one ``[head_dim]`` scale each) and
    ``rope_theta`` a rotary embedding behind it (halves paired; ``forward``
    takes the rows' ``positions``, None counts from 0): the ``qwen3_moe``
    / ``sdar_moe`` attention. ``diffusion_block`` puts the block-diffusion
    structure in the causal mask's place: the rows are a sequence's noisy
    copy and then its clean one (``ops.pallas.flash_attention``).
    ``window`` beside ``causal`` is a sliding window: a row sees itself
    and the ``window - 1`` positions before it (``smallthinker``'s window
    layers; its global layers have neither ``window`` nor ``rope_theta``).

    ``_heads`` takes a projection to ``[B, heads, S, head_dim]``: queries
    and keys of a layer with a norm or a rotation through ``F.qk_heads``
    (one pass; a kernel pair on one TPU at whole 128-lane heads), values
    and a layer with neither by a reshape and a transpose. K/V heads are
    then repeated to ``num_heads`` for the flash dispatch
    (``ops.pallas.flash_attention``; any ``head_dim``); a kernel that
    reads each K/V head once is future work (PERF.md section 7)."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 causal=True, qk_norm_epsilon=None, rope_theta=None,
                 diffusion_block=None, window=None, rope_sections=None):
        super().__init__()
        if num_heads % num_kv_heads:
            raise ValueError(f"GroupedQueryAttention: {num_heads} query "
                             f"heads over {num_kv_heads} key/value heads")
        if diffusion_block is not None and causal:
            raise ValueError("GroupedQueryAttention: the block-diffusion "
                             "structure stands in the causal mask's place; "
                             "give causal=False with diffusion_block")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.causal = head_dim, causal
        self.rope_theta, self.diffusion_block = rope_theta, diffusion_block
        self.window, self.rope_sections = window, rope_sections
        self.q_proj = Linear(hidden_size, num_heads * head_dim,
                             bias_attr=False)
        self.k_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             bias_attr=False)
        self.v_proj = Linear(hidden_size, num_kv_heads * head_dim,
                             bias_attr=False)
        self.q_norm = self.k_norm = None
        if qk_norm_epsilon is not None:
            self.q_norm = RMSNorm(head_dim, qk_norm_epsilon)
            self.k_norm = RMSNorm(head_dim, qk_norm_epsilon)
        self.o_proj = Linear(num_heads * head_dim, hidden_size,
                             bias_attr=False)

    def _heads(self, t, b, s, count, norm=None, rotate=False,
               positions=None):
        """[B, S, count * D] -> [B, num_heads, S, D], through a head norm
        and the rotation (queries and keys, where the layer has them:
        ``F.qk_heads``)."""
        if norm is None and not rotate:
            t = t.reshape([b, s, count, self.head_dim]).transpose(
                [0, 2, 1, 3])
        else:
            weight, epsilon = (None, 0.0) if norm is None \
                else (norm.weight, norm._epsilon)
            t = F.qk_heads(t, count, weight, epsilon,
                           *((positions, self.rope_theta) if rotate else ()),
                           sections=self.rope_sections if rotate else None)
        r = self.num_heads // count
        if r == 1:
            return t
        t = t.unsqueeze(2).expand([b, count, r, s, self.head_dim])
        return t.reshape([b, self.num_heads, s, self.head_dim])

    def qkv(self, x, positions=None):
        """``(q, k, v)`` as the attention op takes them, ``[B, num_heads,
        S, head_dim]`` each."""
        b, s = x.shape[0], x.shape[1]
        rotate = self.rope_theta is not None
        q = self._heads(self.q_proj(x), b, s, self.num_heads, self.q_norm,
                        rotate, positions)
        k = self._heads(self.k_proj(x), b, s, self.num_kv_heads, self.k_norm,
                        rotate, positions)
        return q, k, self._heads(self.v_proj(x), b, s, self.num_kv_heads)

    def forward(self, x, positions=None, force_flash=False):
        b, s = x.shape[0], x.shape[1]
        q, k, v = self.qkv(x, positions)
        from ..ops.pallas import flash_attention
        ctx = flash_attention(q, k, v, causal=self.causal, force=force_flash,
                              diffusion_block=self.diffusion_block,
                              window=self.window)
        ctx = ctx.transpose([0, 2, 1, 3]).reshape(
            [b, s, self.num_heads * self.head_dim])
        return self.o_proj(ctx)


class SparseGroupedQueryAttention(GroupedQueryAttention):
    """Grouped-query attention over a LEARNED SELECTION of keys (DeepSeek
    sparse attention, DeepSeek-V3.2-Exp's report section 2, in front of
    grouped-query attention as the ``KeyeVL2`` language model has it): a
    small indexer scores every causal key of every row, a row's
    ``top_k`` best are the keys its heads read, and the indexer is trained
    by a loss of its own, the KL divergence from the attention
    probabilities its selection produced to the soft-max of its scores.

    On ``n = stop_gradient(x)``: ``qI = n W_qI`` as ``indexer_heads``
    heads of ``indexer_head_dim``, ``kI = LayerNorm(n W_kI)`` (one key
    head), both rotated by the FIRST axis of the positions over the whole
    head (halves paired); ``w = n W_w / sqrt(indexer_heads *
    indexer_head_dim)``; ``I(t, s) = sum_j w[t, j] relu(qI[t, j] . kI[s])``.
    ``F.dsa_select`` makes the selection (once a layer: it leaves it as
    packed bits under a name, which a recomputed block's replay unpacks),
    ``flash_attention(selected=)`` attends under it, ``F.dsa_indexer_loss``
    gives the layer's loss. ``forward(x, positions)`` returns ``(y, loss)``:
    the loss reaches the four ``indexer_*`` layers alone, and nothing else
    reaches them (the selection has no gradient).
    ``stats`` (a buffer, int32[2]) adds up, inside a compiled step, the
    selected and the causal (row, key) pairs of every call, in units of
    ``max(1, S // 16)`` pairs so that int32 holds a run's total:
    ``monitor.device_counters.read()`` gives ``dsa.pairs_selected`` and
    ``dsa.pairs_causal``."""

    COUNTERS = ("dsa.pairs_selected", "dsa.pairs_causal")

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim,
                 indexer_heads, indexer_head_dim, top_k,
                 qk_norm_epsilon=None, rope_theta=10000.0,
                 rope_sections=None, indexer_epsilon=1e-6):
        super().__init__(hidden_size, num_heads, num_kv_heads, head_dim,
                         causal=True, qk_norm_epsilon=qk_norm_epsilon,
                         rope_theta=rope_theta, rope_sections=rope_sections)
        import jax.numpy as jnp
        from .layers import LayerNorm
        from .. import monitor
        from ..tensor import Tensor
        self.indexer_heads, self.top_k = indexer_heads, int(top_k)
        self.indexer_q = Linear(hidden_size, indexer_heads * indexer_head_dim,
                                bias_attr=False)
        self.indexer_k = Linear(hidden_size, indexer_head_dim,
                                bias_attr=False)
        self.indexer_k_norm = LayerNorm(indexer_head_dim, indexer_epsilon,
                                        use_pallas=False)
        self.indexer_w = Linear(hidden_size, indexer_heads, bias_attr=False)
        self._w_scale = 1.0 / math.sqrt(indexer_heads * indexer_head_dim)
        self.register_buffer("stats", monitor.device_counters.register(
            self.COUNTERS, Tensor(jnp.zeros((2,), jnp.int32)), owner=self),
            persistable=False)

    def indexer(self, x, positions):
        """``(qI [B, Hi, S, Di], kI [B, S, Di], w [B, S, Hi] float32)``
        of the layer's input, which gets no gradient from them."""
        import jax
        from ..dispatch import apply
        n = apply(jax.lax.stop_gradient, (x,), nondiff=True,
                  name="stop_gradient")
        at = None if positions is None else (
            positions if self.rope_sections is None else positions[0])
        qi = F.qk_heads(self.indexer_q(n), self.indexer_heads, None, 0.0,
                        at, self.rope_theta)
        ki = F.rotary_embedding(self.indexer_k_norm(self.indexer_k(n)), at,
                                theta=self.rope_theta, interleaved=False)
        w = self.indexer_w(n).astype("float32") * self._w_scale
        return qi, ki, w

    def forward(self, x, positions=None, force_flash=False):
        import jax.numpy as jnp
        from ..dispatch import apply
        from ..ops.pallas import flash_attention
        from ..ops.sparse_attention import dsa_indexer_loss, dsa_select
        b, s = x.shape[0], x.shape[1]
        q, k, v = self.qkv(x, positions)
        qi, ki, w = self.indexer(x, positions)
        selected, lse, _, pairs = dsa_select(qi, ki, w, self.top_k)
        unit = max(1, s // 16)
        causal = b * (s * (s + 1) // 2 // unit)
        self.stats.data = self.stats.data + apply(
            lambda n: jnp.stack([jnp.sum(n // unit),
                                 jnp.full((), causal, n.dtype)]),
            (pairs,), nondiff=True, name="dsa_counters").data
        ctx, m, l = flash_attention(q, k, v, causal=True, selected=selected,
                                    force=force_flash)
        loss = dsa_indexer_loss(q.detach(), k.detach(), m, l, selected, qi,
                                ki, w, lse)
        ctx = ctx.transpose([0, 2, 1, 3]).reshape(
            [b, s, self.num_heads * self.head_dim])
        return self.o_proj(ctx), loss


class MultiHeadLatentAttention(Layer):
    """Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 section
    2.1; the ``deepseek_v3`` / ``joyai_llm_flash`` model codes), training
    form: queries and keys/values go through low-rank latents with an RMS
    norm each, and positions enter through a decoupled rotary part —
    per query head ``qk_rope_head_dim`` wide, and ONE rotary key head that
    all heads share:

        c_q = RMSNorm(u W_qa);  [q_nope | q_rope]_h = c_q W_qb
        [c_kv | k_r] = u W_kva;  [k_nope | v]_h = RMSNorm(c_kv) W_kvb
        q_h = [q_nope_h | R(q_rope_h)],  k_h = [k_nope_h | R(k_r)]
        o_h = softmax(q_h k_h^T / sqrt(d_qk) + causal) v_h;  out = [o_h] W_o

    with ``R`` = ``F.rotary_embedding`` (interleaved pairs) and ``d_qk =
    qk_nope_head_dim + qk_rope_head_dim``. Queries and keys are ``d_qk``
    wide and values ``v_head_dim``: the flash dispatch
    (``ops.pallas.flash_attention``) takes the two sizes as they are.
    ``F.mla_heads`` assembles its three operands from ``q_b_proj``'s and
    ``kv_b_proj``'s results and the rotary key head: on one TPU one kernel
    each way (the rotation, the key head behind every head's ``k_nope``,
    the split of K from V and the move to ``[B, heads, S, d]`` in one
    pass), else the chain of transposes, slices, rotations, a broadcast
    and concatenations that stood here. K is still stored ``d_qk`` wide
    with the rotary key head in every head (flash kernels that take
    ``k_nope`` and ``k_rope`` apart are future work, PERF.md section 7).
    No bias.
    Parameter names are the source's (``q_a_proj``, ``q_a_layernorm``,
    ``q_b_proj``, ``kv_a_proj_with_mqa``, ``kv_a_layernorm``,
    ``kv_b_proj``, ``o_proj``). The compressed cache and the absorbed
    decode path of serving are not here."""

    def __init__(self, hidden_size, num_heads, q_lora_rank, kv_lora_rank,
                 qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                 rope_theta=10000.0, epsilon=1e-6):
        super().__init__()
        self.num_heads, self.kv_lora_rank = num_heads, kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim, self.rope_theta = v_head_dim, float(rope_theta)
        qk = qk_nope_head_dim + qk_rope_head_dim
        self.q_a_proj = Linear(hidden_size, q_lora_rank, bias_attr=False)
        self.q_a_layernorm = RMSNorm(q_lora_rank, epsilon)
        self.q_b_proj = Linear(q_lora_rank, num_heads * qk, bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(
            hidden_size, kv_lora_rank + qk_rope_head_dim, bias_attr=False)
        self.kv_a_layernorm = RMSNorm(kv_lora_rank, epsilon)
        self.kv_b_proj = Linear(
            kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim),
            bias_attr=False)
        self.o_proj = Linear(num_heads * v_head_dim, hidden_size,
                             bias_attr=False)

    def qkv(self, x):
        """``(q, k, v)`` as the attention op takes them: ``[B, heads, S,
        d_qk]`` twice and ``[B, heads, S, v_head_dim]`` (``F.mla_heads``)."""
        q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(x)))
        ckv = self.kv_a_proj_with_mqa(x)
        kv = self.kv_b_proj(self.kv_a_layernorm(
            ckv[:, :, :self.kv_lora_rank]))
        return F.mla_heads(q, kv, ckv[:, :, self.kv_lora_rank:],
                           self.num_heads, self.qk_nope_head_dim,
                           self.v_head_dim, theta=self.rope_theta)

    def forward(self, x, force_flash=False):
        b, s = x.shape[0], x.shape[1]
        q, k, v = self.qkv(x)
        from ..ops.pallas import flash_attention
        ctx = flash_attention(q, k, v, causal=True, force=force_flash)
        ctx = ctx.transpose([0, 2, 1, 3]).reshape(
            [b, s, self.num_heads * self.v_head_dim])
        return self.o_proj(ctx)


# -- the phi4flash layers (decoder-hybrid-decoder, arXiv:2507.06607) --------
# At the end of the file on purpose: the flash call sites above keep their
# line numbers (a Mosaic kernel's cache key carries its caller's lines).

__all__ += ["MambaMixer", "DifferentialAttention", "GatedMemoryUnit"]


class MambaMixer(Layer):
    """Mamba-1 mixer (Gu & Dao, arXiv:2312.00752) as the ``mamba`` /
    ``phi4flash`` model codes lay it out: ``[x | z] = u W_in``; a causal
    depthwise convolution of ``conv_kernel`` taps with bias and SiLU over
    ``x``; ``[dt_r | B | C] = x W_x`` (``dt_rank + 2 state_size`` wide);
    the step sizes ``softplus(dt_r W_dt + b_dt)``, one a (token, channel);
    ``A = -exp(A_log)`` ``[inner, state_size]``, a transition for every
    (channel, state) pair; the selective recurrence (``F.selective_scan``);
    the gate ``silu(z)``; ``W_out``. Biases: the convolution's and
    ``dt_proj``'s, no other. ``forward(u, return_memory=True)`` also
    returns the scan's result BEFORE the gate, ``[B, S, inner]``: what a
    decoder-hybrid-decoder's gated memory units read."""

    def __init__(self, hidden_size, inner, state_size=16, conv_kernel=4,
                 dt_rank=None, time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4):
        super().__init__()
        self.inner, self.state_size = inner, state_size
        self.dt_rank = dt_rank or -(-hidden_size // 16)
        self.in_proj = Linear(hidden_size, 2 * inner, bias_attr=False)
        tap = 1.0 / math.sqrt(conv_kernel)
        self.conv_weight = self.create_parameter(
            (inner, conv_kernel), default_initializer=I.Uniform(-tap, tap))
        self.conv_bias = self.create_parameter(
            (inner,), default_initializer=I.Uniform(-tap, tap))
        self.x_proj = Linear(inner, self.dt_rank + 2 * state_size,
                             bias_attr=False)
        self.dt_proj = Linear(self.dt_rank, inner)
        # the step sizes start log-uniform in [min, max] (softplus^-1 of
        # them in dt_proj's bias), A_log = log(1..N) in every channel, D = 1
        rng = np.random.default_rng(0)
        dt = np.maximum(np.exp(rng.uniform(math.log(time_step_min),
                                           math.log(time_step_max), inner)),
                        time_step_floor)
        self.dt_proj.bias.set_value(
            (dt + np.log(-np.expm1(-dt))).astype("float32"))
        self.A_log = self.create_parameter(
            (inner, state_size), default_initializer=I.Assign(np.tile(np.log(
                np.arange(1, state_size + 1, dtype="float32")), (inner, 1))))
        self.D = self.create_parameter(
            (inner,), default_initializer=I.Constant(1.0))
        self.out_proj = Linear(inner, hidden_size, bias_attr=False)

    def forward(self, u, return_memory=False):
        n, r = self.state_size, self.dt_rank
        xz = self.in_proj(u)
        x = S.causal_conv1d(xz[:, :, :self.inner], self.conv_weight,
                            self.conv_bias, activation="silu")
        dbc = self.x_proj(x)
        gated, y = S.selective_scan(
            x, F.linear(dbc[:, :, :r], self.dt_proj.weight), self.A_log,
            dbc[:, :, r:r + n], dbc[:, :, r + n:], self.D,
            dt_bias=self.dt_proj.bias, z=xz[:, :, self.inner:])
        out = self.out_proj(gated)
        return (out, y) if return_memory else out


class DifferentialAttention(GroupedQueryAttention):
    """Differential attention (Ye et al., arXiv:2410.05258) over
    grouped-query heads, as the ``phi4flash`` model code has it: the
    heads pair up as ``(2j, 2j + 1)``, query pair ``j`` reads key/value
    pair ``j // r`` (``r = num_heads / num_kv_heads``), and a pair gives

        A1 = softmax(mask(q_2j k_2g^T / sqrt(D))),  A2 = ... q_2j+1 k_2g+1^T
        o_j = (1 - lambda_init) RMSNorm_2D((A1 - lambda A2) [v_2g | v_2g+1])

    with ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init`` (four
    learned ``[D]`` vectors a layer), ``lambda_init = 0.8 - 0.6 exp(-0.3
    depth)`` by the layer's SOURCE index ``depth``, and ONE learned scale
    ``[2 D]`` a layer in the pair norm (``subln``). One flash call of
    ``num_heads`` heads at ``D`` | ``2 D`` computes both maps: the pairing
    is in which K head and which V pair a query head is given; ``lambda``,
    the subtraction (its two terms nearly cancel) and the pair norm are
    float32 (``F.differential_heads``). ``window`` beside the causal mask
    is a sliding window. Every projection has a BIAS. ``cross=True`` makes
    queries only: ``forward(x, kv=(k, v))`` then attends over another
    layer's keys and values, as ``key_value(x)`` of that layer gives them
    (``[B, num_kv_heads, S, D]`` and ``[B, num_kv_heads / 2, S, 2 D]``);
    ``forward(x, return_kv=True)`` returns them beside the result."""

    def __init__(self, hidden_size, num_heads, num_kv_heads, head_dim, depth,
                 window=None, cross=False, epsilon=1e-5):
        Layer.__init__(self)
        if num_heads % num_kv_heads or num_kv_heads % 2:
            raise ValueError(f"DifferentialAttention: {num_heads} query "
                             f"heads over {num_kv_heads} key/value heads "
                             f"do not pair up")
        self.num_heads, self.num_kv_heads = num_heads, num_kv_heads
        self.head_dim, self.window, self.cross = head_dim, window, cross
        self.lambda_init = 0.8 - 0.6 * math.exp(-0.3 * depth)
        self.q_proj = Linear(hidden_size, num_heads * head_dim)
        if not cross:
            self.k_proj = Linear(hidden_size, num_kv_heads * head_dim)
            self.v_proj = Linear(hidden_size, num_kv_heads * head_dim)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                (head_dim,), default_initializer=I.Normal(0.0, 0.1)))
        self.subln = RMSNorm(2 * head_dim, epsilon)
        self.o_proj = Linear(num_heads * head_dim, hidden_size)

    def key_value(self, x):
        """``(k [B, num_kv_heads, S, D], v [B, num_kv_heads / 2, S, 2 D])``
        of the layer's input: each key head once, the value heads of a
        pair side by side."""
        b, s = x.shape[0], x.shape[1]
        k = self.k_proj(x).reshape(
            [b, s, self.num_kv_heads, self.head_dim]).transpose([0, 2, 1, 3])
        v = self.v_proj(x).reshape(
            [b, s, self.num_kv_heads // 2, 2 * self.head_dim]).transpose(
                [0, 2, 1, 3])
        return k, v

    def _served(self, k, v):
        """K and V as the flash dispatch takes them, a head a query head:
        query head ``2j + i`` reads key head ``2 (j // r) + i`` and value
        pair ``j // r``."""
        b, _, s, d = k.shape
        pairs, r = self.num_kv_heads // 2, self.num_heads // self.num_kv_heads
        k = k.reshape([b, pairs, 1, 2, s, d]).expand([b, pairs, r, 2, s, d])
        v = v.unsqueeze(2).expand([b, pairs, 2 * r, s, 2 * d])
        return (k.reshape([b, self.num_heads, s, d]),
                v.reshape([b, self.num_heads, s, 2 * d]))

    def forward(self, x, kv=None, return_kv=False, force_flash=False):
        from ..ops.pallas import flash_attention
        b, s = x.shape[0], x.shape[1]
        if (kv is None) == self.cross:
            raise ValueError("DifferentialAttention: a cross layer takes "
                             "kv=(k, v), a self layer makes its own")
        q = self._heads(self.q_proj(x), b, s, self.num_heads)
        kv = self.key_value(x) if kv is None else kv
        ctx = flash_attention(q, *self._served(*kv), causal=True,
                              window=self.window, force=force_flash,
                              scale=1.0 / math.sqrt(self.head_dim))
        out = self.o_proj(F.differential_heads(
            ctx, self.lambda_q1, self.lambda_k1, self.lambda_q2,
            self.lambda_k2, self.subln.weight, self.lambda_init,
            self.subln._epsilon))
        return (out,) + tuple(kv) if return_kv else out


class GatedMemoryUnit(Layer):
    """The gated memory unit of a decoder-hybrid-decoder (SambaY,
    arXiv:2507.06607 section 2): ``W_2 (silu(W_1 u) * M)`` with ``M``
    ``[B, S, memory_size]`` ANOTHER layer's scan result, taken before its
    gate (``MambaMixer.forward(return_memory=True)``): an element-wise
    gate on a memory that was made once, in place of a token mixer. No
    bias."""

    def __init__(self, hidden_size, memory_size):
        super().__init__()
        self.in_proj = Linear(hidden_size, memory_size, bias_attr=False)
        self.out_proj = Linear(memory_size, hidden_size, bias_attr=False)

    def forward(self, u, memory):
        return self.out_proj(F.silu(self.in_proj(u)) * memory)
