"""LFM2-MoE: a causal language model whose stack mixes gated short
convolutions with grouped-query attention, over a dense layer and then
routed gated experts, with a tied head (``model_type: lfm2_moe``; the
published ``config.json`` of LiquidAI/LFM2-8B-A1B names the sizes and the
per-layer list, the ``lfm2`` / ``lfm2_moe`` model codes the layer).

No reference counterpart in Paddle Fluid 1.7. Every block is pre-norm on
one residual stream:

    h = x + Op(RMSNorm_op(x))          y = h + FF(RMSNorm_ffn(h))

``Op`` is chosen layer by layer by ``layer_types``: ``"conv"`` is
:class:`nn.GatedShortConv` (``[b | c | u] = n W_in``, ``(c * conv3(b * u))
W_out``: a causal depthwise convolution of ``conv_L_cache`` taps, no bias,
no activation), ``"full_attention"`` :class:`nn.GroupedQueryAttention`
with an RMS norm over each query and key head and a half-split rotary
embedding behind it. ``FF`` is a :class:`nn.GatedMLP` of
``intermediate_size`` in the first ``num_dense_layers`` layers and after
them a gated :class:`nn.RoutedMoE` of ``moe_intermediate_size``: sigmoid
scores over all experts, a fixed selection bias (``use_expert_bias``),
top-k renormalised over the chosen and scaled by
``routed_scaling_factor``, no shared expert. Then ``RMSNorm`` (the
source's ``embedding_norm``) and the head, which IS the embedding:
``logits = h E^T``, one leaf that takes the look-up's gradient and the
head's. Loss: the mean next-token cross entropy.

Parameter names follow the source's state dict without its ``model.``
prefix where the layers here have the source's parts (``embed_tokens``,
``embedding_norm``, ``layers.<i>.operator_norm / ffn_norm / conv.in_proj /
conv.out_proj / self_attn.q_proj / k_proj / v_proj``); ``conv.conv_weight``
is ``[channels, taps]`` (the source's ``conv.conv.weight`` without its
middle axis), attention's way out and head norms are
``nn.GroupedQueryAttention``'s (``o_proj``, ``q_norm``, ``k_norm`` for the
source's ``out_proj``, ``q_layernorm``, ``k_layernorm``), the dense layer's
``nn.GatedMLP``'s (``gate_proj / up_proj / down_proj`` for ``w1 / w3 /
w2``) and the expert layer's ``nn.RoutedMoE``'s (``feed_forward.router.
weight`` for ``feed_forward.gate.weight``, the experts stacked,
``feed_forward.experts_gate / experts_up / experts_down`` ``[held, in,
out]``).

**A chip's share**, as ``models/sdar_moe.py`` has it: ``num_experts``
counts the experts HELD here, ``first_expert_held`` the first of them,
``num_experts_published`` the router's width (None: all are held);
``vocab_size`` is the slice of the vocabulary held here. ``layer_types``
may be the published 24 entries beside fewer layers: layer ``i`` here is
the source's layer ``first_layer + i``, and the first ``num_dense_layers``
layers HERE are dense.

Under ``amp.auto_cast`` the residual stream is in the compute dtype;
router, rotary angles, every norm's statistics and the loss stay float32.
``recompute`` checkpoints each block (``jit.recompute``). The serving side
(a cache of ``conv_L_cache - 1`` rows a conv layer beside the attention
layers' keys) is not here.
"""
from __future__ import annotations

from .. import amp, nn, ops
from .. import initializer as I
from ..ops import manip

_KINDS = ("conv", "full_attention")
# the published list: periods of conv conv full_attention conv, the last
# irregular
_PUBLISHED = tuple(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv"
    for i in range(24))


class Lfm2MoeConfig:
    """The published keys (defaults: LFM2-8B-A1B) and what says which
    share of the model this is."""

    def __init__(self, vocab_size=65536, hidden_size=2048,
                 intermediate_size=7168, moe_intermediate_size=1792,
                 num_hidden_layers=24, num_dense_layers=2, layer_types=None,
                 first_layer=0, num_attention_heads=32,
                 num_key_value_heads=8, rope_theta=1000000.0, norm_eps=1e-5,
                 conv_L_cache=3, conv_bias=False, num_experts=32,
                 num_experts_per_tok=4, norm_topk_prob=True,
                 use_expert_bias=True, routed_scaling_factor=1.0,
                 initializer_range=0.02, num_experts_published=None,
                 first_expert_held=0, recompute=False):
        if num_experts_published is None:
            num_experts_published = num_experts
        if first_expert_held < 0 or num_experts < 1 or \
                first_expert_held + num_experts > num_experts_published:
            raise ValueError(
                f"experts {first_expert_held} .. {first_expert_held} + "
                f"{num_experts} are not a range of the "
                f"{num_experts_published} published")
        if conv_bias or not (norm_topk_prob and use_expert_bias):
            raise ValueError(
                "only the source's forms are written: a convolution "
                "without bias, a sigmoid router with a selection bias, "
                "top-k renormalised over the chosen")
        given = _PUBLISHED if layer_types is None else tuple(layer_types)
        layer_types = given[first_layer:first_layer + num_hidden_layers]
        if first_layer < 0 or len(layer_types) < num_hidden_layers \
                or set(layer_types) - set(_KINDS):
            raise ValueError(
                f"layer_types gives no kind of {_KINDS} for each of the "
                f"{num_hidden_layers} layers from {first_layer} on: "
                f"{given!r}")
        if not 0 <= num_dense_layers <= num_hidden_layers:
            raise ValueError(f"num_dense_layers {num_dense_layers} is not "
                             f"within the {num_hidden_layers} layers")
        self.__dict__.update(
            {k: v for k, v in locals().items() if k not in ("self", "given")})

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                 moe_intermediate_size=32, num_hidden_layers=5,
                 num_dense_layers=1, first_layer=1, num_attention_heads=4,
                 num_key_value_heads=2, rope_theta=10000.0, num_experts=4,
                 num_experts_published=16, num_experts_per_tok=3)
        d.update(kw)
        return Lfm2MoeConfig(**d)


class Lfm2MoeBlock(nn.Layer):
    def __init__(self, config, layer):
        super().__init__()
        c = config
        self.operator_norm = nn.RMSNorm(c.hidden_size, c.norm_eps)
        if c.layer_types[layer] == "conv":
            self.conv = nn.GatedShortConv(c.hidden_size, c.conv_L_cache)
        else:
            self.self_attn = nn.GroupedQueryAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.hidden_size // c.num_attention_heads, causal=True,
                qk_norm_epsilon=c.norm_eps, rope_theta=c.rope_theta)
        self.ffn_norm = nn.RMSNorm(c.hidden_size, c.norm_eps)
        if layer < c.num_dense_layers:
            self.feed_forward = nn.GatedMLP(c.hidden_size,
                                            c.intermediate_size)
        else:
            self.feed_forward = nn.RoutedMoE(
                c.hidden_size, c.moe_intermediate_size,
                c.num_experts_published, c.num_experts_per_tok,
                experts_held=range(c.first_expert_held,
                                   c.first_expert_held + c.num_experts),
                routed_scaling_factor=c.routed_scaling_factor, gated=True,
                scoring="sigmoid")

    def forward(self, x):
        op = self.conv if hasattr(self, "conv") else self.self_attn
        h = x + op(self.operator_norm(x))
        return h + self.feed_forward(self.ffn_norm(h))


class Lfm2MoeForCausalLM(nn.Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [Lfm2MoeBlock(config, i)
             for i in range(config.num_hidden_layers)])
        self.embedding_norm = nn.RMSNorm(config.hidden_size, config.norm_eps)
        # every matrix normal(0, initializer_range), norm scales 1
        init = I.Normal(0.0, config.initializer_range)
        for _, p in self.named_parameters():
            if len(p.shape) >= 2:
                p.set_value(init(tuple(p.shape), "float32"))

    def forward(self, input_ids):
        from .. import jit
        h = self.embed_tokens(input_ids)
        if amp.is_enabled():
            h = h.astype(amp.compute_dtype())
        for block in self.layers:
            h = jit.recompute(block, h) if self.config.recompute \
                else block(h)
        # the head is the embedding: one leaf, two gradients
        return ops.matmul(self.embedding_norm(h), self.embed_tokens.weight,
                          transpose_y=True)

    def loss(self, logits, input_ids):
        """Mean next-token cross entropy over the predicted positions of
        every sequence: position t's logits against token t + 1. The
        labels are shifted and a sequence's last position's is the loss's
        ``ignore_index``, so that the logits stay whole
        (``models/smallthinker.py``)."""
        b = input_ids.shape[0]
        labels = manip.concat(
            [input_ids[:, 1:], ops.full([b, 1], -100, dtype=input_ids.dtype)],
            axis=1)
        return ops.loss.cross_entropy(logits, labels, ignore_index=-100)
