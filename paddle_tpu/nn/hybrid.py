"""Sequence mixers of hybrid and latent-attention language models: a
Mamba-2 state-space mixer, a gated short convolution, grouped-query
attention and multi-head latent attention.

No reference counterpart in Paddle Fluid 1.7. All map ``[B, S, hidden]``
to ``[B, S, hidden]`` with no bias, no dropout and no cache: the training
path. (A state cache for serving is future work, PERF.md section 7.)
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
