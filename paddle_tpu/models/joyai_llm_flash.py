"""JoyAI-LLM-Flash: a DeepSeek-V3-style causal language model — multi-head
latent attention with a decoupled rotary part in every block, one leading
dense gated MLP block, then routed mixtures of gated experts with one
shared expert, and a multi-token-prediction module (``model_type:
joyai_llm_flash``; the published ``config.json`` of
jdopensource/JoyAI-LLM-Flash names the keys, the DeepSeek-V3 report,
arXiv:2412.19437, sections 2.1-2.2, the equations).

No reference counterpart in Paddle Fluid 1.7. Every block is pre-norm on
one residual stream: ``h <- h + Attn(RMSNorm(h))``, then ``h <- h +
FFN(RMSNorm(h))``; ``Attn`` is :class:`nn.MultiHeadLatentAttention`,
``FFN`` a :class:`nn.GatedMLP` in the first ``first_k_dense_replace``
blocks and a gated :class:`nn.RoutedMoE` after them. ``logits =
Head(RMSNorm(h^L))``, the head untied from the embedding.

**Multi-token prediction.** With ``h^L`` the main stack's output BEFORE
the final norm, the module (layer ``L`` of ``layers``, weights of its
own) computes ``x_t = W_eh [RMSNorm_e(Emb(id_{t+1})) ; RMSNorm_h(h^L_t)]``,
``z = Block(x)``, ``mtp_logits_t = Head(RMSNorm_s(z_t))``, which predicts
``id_{t+2}``; ``Emb`` and ``Head`` are the main model's. It runs over all
``S`` positions (the last one is fed ``id_0`` and is left out of the loss:
attention is causal, so nothing earlier sees it).
``loss = CE(logits_t, id_{t+1}) + mtp_loss_weight * CE(mtp_logits_t,
id_{t+2})``, each a mean over its own positions.

Parameter names follow the source's state dict without its ``model.``
prefix (``layers.<i>.self_attn.q_a_proj.weight``,
``...kv_a_proj_with_mqa...``, ``layers.<i>.mlp.shared_experts.gate_proj.
weight``, ``layers.<L>.eh_proj / enorm / hnorm / shared_head.norm``),
except where ``nn.RoutedMoE`` names its own: the router is ``mlp.router.
weight`` (the source's ``mlp.gate.weight``) and the experts are stacked,
``mlp.experts_gate / experts_up / experts_down`` ``[held, in, out]``.

**A chip's share**, as ``models/nemotron_h.py`` has it: ``n_routed_experts``
counts the experts HELD here, ``first_expert_held`` the first of them,
``n_routed_experts_published`` the router's width (None: all are held);
``vocab_size`` is the slice of the vocabulary held here.

Under ``amp.auto_cast`` the residual stream is in the compute dtype;
router, rotary angles and every norm's statistics stay float32.
``recompute`` checkpoints each block and the module's body
(``jit.recompute``).
"""
from __future__ import annotations

from .. import amp, nn, ops
from .. import initializer as I
from ..ops import manip


class JoyAIFlashConfig:
    """The published keys (defaults: JoyAI-LLM-Flash), plus what says
    which share of the model this is."""

    def __init__(self, vocab_size=129280, hidden_size=2048,
                 intermediate_size=7168, moe_intermediate_size=768,
                 num_hidden_layers=40, num_nextn_predict_layers=1,
                 num_attention_heads=32, q_lora_rank=1536, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=32000000.0, rope_interleave=True,
                 n_routed_experts=256, n_shared_experts=1,
                 num_experts_per_tok=8, first_k_dense_replace=1,
                 moe_layer_freq=1, routed_scaling_factor=2.5,
                 rms_norm_eps=1e-6, initializer_range=0.02,
                 n_routed_experts_published=None, first_expert_held=0,
                 recompute=False, mtp_loss_weight=0.3):
        if n_routed_experts_published is None:
            n_routed_experts_published = n_routed_experts
        if not 0 <= first_k_dense_replace <= num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace {first_k_dense_replace} is not "
                f"within the {num_hidden_layers} layers")
        if first_expert_held < 0 or n_routed_experts < 1 or \
                first_expert_held + n_routed_experts \
                > n_routed_experts_published:
            raise ValueError(
                f"experts {first_expert_held} .. {first_expert_held} + "
                f"{n_routed_experts} are not a range of the "
                f"{n_routed_experts_published} published")
        if num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers: 0 or 1 module of "
                             "multi-token prediction is written, not "
                             f"{num_nextn_predict_layers}")
        if not rope_interleave:
            raise ValueError("rope_interleave false: only the source's "
                             "interleaved rotary layout is written")
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != "self"})

    @property
    def pattern(self):
        """``D`` (dense MLP) or ``E`` (routed experts) a main layer, as the
        source's code decides it."""
        return "".join(
            "E" if i >= self.first_k_dense_replace
            and i % self.moe_layer_freq == 0 else "D"
            for i in range(self.num_hidden_layers))

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_hidden_layers=3,
                 num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
                 qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 rope_theta=10000.0, n_routed_experts=4,
                 n_routed_experts_published=16, num_experts_per_tok=3)
        d.update(kw)
        return JoyAIFlashConfig(**d)


class JoyAIFlashBlock(nn.Layer):
    def __init__(self, config, dense):
        super().__init__()
        c = config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = nn.MultiHeadLatentAttention(
            c.hidden_size, c.num_attention_heads, c.q_lora_rank,
            c.kv_lora_rank, c.qk_nope_head_dim, c.qk_rope_head_dim,
            c.v_head_dim, rope_theta=c.rope_theta, epsilon=c.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        if dense:
            self.mlp = nn.GatedMLP(c.hidden_size, c.intermediate_size)
        else:
            self.mlp = nn.RoutedMoE(
                c.hidden_size, c.moe_intermediate_size,
                c.n_routed_experts_published, c.num_experts_per_tok,
                d_shared=c.n_shared_experts * c.moe_intermediate_size,
                experts_held=range(c.first_expert_held,
                                   c.first_expert_held + c.n_routed_experts),
                routed_scaling_factor=c.routed_scaling_factor, gated=True)

    def forward(self, h):
        h = h + self.self_attn(self.input_layernorm(h))
        return h + self.mlp(self.post_attention_layernorm(h))


class SharedHead(nn.Layer):
    """The prediction module's way out: a norm of its own in front of the
    head it shares with the main model."""

    def __init__(self, config):
        super().__init__()
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)

    def forward(self, z, head):
        return head(self.norm(z))


class MultiTokenPredictor(JoyAIFlashBlock):
    """One multi-token-prediction module (DeepSeek-V3 report, section
    2.2): an expert block with, in front of it, the projection ``W_eh`` of
    the next token's normed embedding beside the normed hidden state.
    ``forward(h, e)`` is the body, ``h`` the stack's output before its
    final norm and ``e = Emb(id_{t+1})``; ``shared_head(z, head)`` gives
    the logits."""

    def __init__(self, config):
        super().__init__(config, dense=False)
        d = config.hidden_size
        self.enorm = nn.RMSNorm(d, config.rms_norm_eps)
        self.hnorm = nn.RMSNorm(d, config.rms_norm_eps)
        self.eh_proj = nn.Linear(2 * d, d, bias_attr=False)
        self.shared_head = SharedHead(config)

    def forward(self, h, e):
        x = self.eh_proj(manip.concat([self.enorm(e), self.hnorm(h)],
                                      axis=-1))
        return super().forward(x)


class JoyAIFlashForCausalLM(nn.Layer):
    def __init__(self, config: JoyAIFlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [JoyAIFlashBlock(config, kind == "D") for kind in config.pattern]
            + [MultiTokenPredictor(config)
               for _ in range(config.num_nextn_predict_layers)])
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

    def forward(self, input_ids):
        """``(logits, mtp_logits)``, both ``[B, S, vocab]``; ``mtp_logits``
        is None for a model without the module."""
        from .. import jit
        c = self.config
        run = jit.recompute if c.recompute else (lambda layer, *a: layer(*a))
        cast = (lambda t: t.astype(amp.compute_dtype())) if amp.is_enabled() \
            else (lambda t: t)
        h = cast(self.embed_tokens(input_ids))
        for block in self.layers[:c.num_hidden_layers]:
            h = run(block, h)
        logits = self.lm_head(self.norm(h))
        if not c.num_nextn_predict_layers:
            return logits, None
        mtp = self.layers[c.num_hidden_layers]
        # id_{t+1} at position t; the last position wraps to id_0 and is
        # left out of the loss
        following = manip.concat([input_ids[:, 1:], input_ids[:, :1]],
                                 axis=1)
        z = run(mtp, h, cast(self.embed_tokens(following)))
        return logits, mtp.shared_head(z, self.lm_head)

    def loss(self, logits, mtp_logits, input_ids):
        """Mean next-token cross entropy over positions ``t <= S - 2``,
        plus ``mtp_loss_weight`` times the module's mean cross entropy
        against token ``t + 2`` over ``t <= S - 3``."""
        s = input_ids.shape[1]
        loss = ops.loss.cross_entropy(logits[:, :s - 1], input_ids[:, 1:])
        if mtp_logits is None:
            return loss
        return loss + self.config.mtp_loss_weight * ops.loss.cross_entropy(
            mtp_logits[:, :s - 2], input_ids[:, 2:])
