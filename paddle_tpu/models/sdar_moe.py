"""SDAR-MoE: a Qwen3-MoE-shaped language model trained by block diffusion
(``model_type: sdar_moe``; the published ``config.json`` of
JetLM/SDAR-30B-A3B-Chat names the sizes, the ``sdar_moe`` / ``qwen3_moe``
model codes the layer, Arriola et al., "Block Diffusion", arXiv:2503.09573
section 3, the training pass).

No reference counterpart in Paddle Fluid 1.7. Every block is pre-norm on
one residual stream: ``h <- h + Attn(RMSNorm(h))``, then ``h <- h +
MoE(RMSNorm(h))``. ``Attn`` is :class:`nn.GroupedQueryAttention` with an
RMS norm over each query and key head, a half-split rotary embedding and
the block-diffusion structure in the causal mask's place; ``MoE`` a gated
:class:`nn.RoutedMoE` with the soft-max router and no shared expert.

**The training pass.** A sequence of ``L`` tokens goes through the stack
as ``2 L`` rows: its NOISY copy (some tokens replaced by ``mask_token_id``)
in rows ``[0, L)`` and its CLEAN copy behind it, both copies of token ``i``
at position ``i``. With ``block_length`` positions a block, a clean row
sees the clean blocks up to its own, a noisy row the clean blocks before
its own and its own noisy block, both directions inside a block. Only the
noisy copy's rows go through the head: ``logits = Head(RMSNorm(h[:L]))``,
and ``loss = (1 / L) sum_i w_i CE(logits_i, clean_i)`` with ``w_i = 1 /
t`` at a position masked at noise level ``t`` and 0 elsewhere, no shift
(``F.block_diffusion_loss``). Which tokens are masked, and the weights,
are the data's to say: ``forward(noisy_ids, clean_ids)``, ``loss(logits,
clean_ids, weights)``. Generation (a block of tokens over several
denoising passes) needs a decoder on the serving path and is not here.

Parameter names follow the source's state dict without its ``model.``
prefix (``layers.<i>.self_attn.q_proj / k_proj / v_proj / o_proj / q_norm
/ k_norm``, ``input_layernorm``, ``post_attention_layernorm``), except
where ``nn.RoutedMoE`` names its own: the router is ``mlp.router.weight``
(the source's ``mlp.gate.weight``) and the experts are stacked,
``mlp.experts_gate / experts_up / experts_down`` ``[held, in, out]``.

**A chip's share**, as ``models/nemotron_h.py`` has it: ``num_experts``
counts the experts HELD here, ``first_expert_held`` the first of them,
``num_experts_published`` the router's width (None: all are held);
``vocab_size`` is the slice of the vocabulary held here.

``stats`` (a buffer, int32[2]) adds up, inside the compiled step, the
positions that carried a loss weight and the calls of ``loss``:
``monitor.device_counters.read()`` gives ``diffusion.masked_rows`` and
``diffusion.steps``.

Under ``amp.auto_cast`` the residual stream is in the compute dtype;
router, rotary angles, every norm's statistics and the loss stay float32.
``recompute`` checkpoints each block (``jit.recompute``).
"""
from __future__ import annotations

from .. import amp, nn, ops
from .. import initializer as I
from ..ops import manip
from ..tensor import Tensor


class SDARMoEConfig:
    """The published keys (defaults: SDAR-30B-A3B-Chat), what the training
    pass needs beside them, and what says which share of the model this
    is."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 moe_intermediate_size=768, num_hidden_layers=48,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 rope_theta=1000000.0, num_experts=128,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 rms_norm_eps=1e-6, initializer_range=0.02, block_length=4,
                 mask_token_id=None, num_experts_published=None,
                 first_expert_held=0, recompute=False):
        if num_experts_published is None:
            num_experts_published = num_experts
        if mask_token_id is None:
            mask_token_id = vocab_size - 1
        if first_expert_held < 0 or num_experts < 1 or \
                first_expert_held + num_experts > num_experts_published:
            raise ValueError(
                f"experts {first_expert_held} .. {first_expert_held} + "
                f"{num_experts} are not a range of the "
                f"{num_experts_published} published")
        if not norm_topk_prob:
            raise ValueError("norm_topk_prob false: only the source's "
                             "renormalised top-k weights are written")
        if block_length < 1 or block_length & (block_length - 1):
            raise ValueError(f"block_length {block_length} is not a power "
                             f"of two")
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != "self"})

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
                 num_experts=4, num_experts_published=16,
                 num_experts_per_tok=3, block_length=4)
        d.update(kw)
        return SDARMoEConfig(**d)


class SDARMoEBlock(nn.Layer):
    def __init__(self, config):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = nn.GroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, causal=False, qk_norm_epsilon=c.rms_norm_eps,
            rope_theta=c.rope_theta, diffusion_block=c.block_length)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.mlp = nn.RoutedMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts_published,
            c.num_experts_per_tok,
            experts_held=range(c.first_expert_held,
                               c.first_expert_held + c.num_experts),
            gated=True, scoring="softmax")

    def forward(self, h):
        """``h`` [B, 2 L, hidden]: the noisy copy's rows, then the clean
        copy's; both copies of a token stand at its position."""
        at = ops.arange(h.shape[1] // 2, dtype="int32")
        h = h + self.self_attn(self.input_layernorm(h),
                               positions=manip.concat([at, at]))
        return h + self.mlp(self.post_attention_layernorm(h))


class SDARMoEForBlockDiffusion(nn.Layer):
    COUNTERS = ("diffusion.masked_rows", "diffusion.steps")

    def __init__(self, config: SDARMoEConfig):
        super().__init__()
        import jax.numpy as jnp
        from .. import monitor
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [SDARMoEBlock(config) for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        # every matrix normal(0, initializer_range), norm scales 1; no
        # projection is rescaled by the depth (the source's family has no
        # such rule)
        init = I.Normal(0.0, config.initializer_range)
        for _, p in self.named_parameters():
            if len(p.shape) >= 2:
                p.set_value(init(tuple(p.shape), "float32"))
        self.register_buffer("stats", monitor.device_counters.register(
            self.COUNTERS, Tensor(jnp.zeros((len(self.COUNTERS),),
                                            jnp.int32)), owner=self),
            persistable=False)

    def forward(self, noisy_ids, clean_ids):
        """Logits ``[B, L, vocab]`` of the noisy copy's rows; the clean
        copy's rows give keys and values and no logits."""
        from .. import jit
        c = self.config
        seq = noisy_ids.shape[1]
        h = self.embed_tokens(manip.concat([noisy_ids, clean_ids], axis=1))
        if amp.is_enabled():
            h = h.astype(amp.compute_dtype())
        for block in self.layers:
            h = jit.recompute(block, h) if c.recompute else block(h)
        return self.lm_head(self.norm(h[:, :seq]))

    def loss(self, logits, clean_ids, weights):
        """``(1 / (B L)) sum w_i CE(logits_i, clean_i)``: no shift, the
        weights say which positions were masked and at which noise."""
        import jax.numpy as jnp
        from ..dispatch import apply
        seen = apply(lambda w: jnp.stack(
            [jnp.sum(w > 0, dtype=jnp.int32), jnp.ones((), jnp.int32)]),
            (weights,), name="diffusion_counters")
        self.stats.data = self.stats.data + seen.data
        return ops.loss.block_diffusion_loss(logits, clean_ids, weights)
