"""SmallThinker: a causal language model whose stack mixes two kinds of
attention layer, with a router that reads ahead of attention
(``model_name: smallthinker_21b_instruct``; the published ``config.json``
of PowerInfer/SmallThinker-21BA3B-Instruct names the sizes and the two
per-layer patterns, the ``smallthinker`` model code the layer).

No reference counterpart in Paddle Fluid 1.7. Every block is pre-norm on
one residual stream, and its router scores the block's INPUT, ahead of the
norm and of attention (so that a deployment can fetch the chosen experts
while attention runs):

    r = x W_r                                  (float32, the un-normalised x)
    h = x + Attn(RMSNorm(x))
    y = h + sum_{e in top-k of softmax(r), held here} w_e Expert_e(RMSNorm(h))

``Attn`` is :class:`nn.GroupedQueryAttention` without head norms, in one
of two forms that the configuration's two lists choose layer by layer:
``sliding_window_layout[l] == 1`` gives the layer a sliding window (a row
sees itself and the ``sliding_window_size - 1`` positions before it),
``rope_layout[l] == 1`` a half-split rotary embedding; a layer with
neither is global and has NO position embedding. Published: ``[0, 1, 1,
1]`` repeated for both, one global position-free layer and three rotated
window layers a period. ``Expert_e(m) = W_down,e (relu(W_gate,e m) *
W_up,e m)``: a gated :class:`nn.RoutedMoE` with the soft-max router
(renormalised over the chosen), the ReLU gate and no shared expert. Then
``RMSNorm``, an untied head, and the mean next-token cross entropy.

Parameter names follow the source's state dict without its ``model.``
prefix (``layers.<i>.self_attn.q_proj / k_proj / v_proj / o_proj``,
``input_layernorm``, ``post_attention_layernorm``, ``block_sparse_moe``),
except where ``nn.RoutedMoE`` names its own: the router is
``block_sparse_moe.router.weight`` (the source's ``primary_router``) and
the experts are stacked, ``block_sparse_moe.experts_gate / experts_up /
experts_down`` ``[held, in, out]``.

**A chip's share**, as ``models/sdar_moe.py`` has it:
``moe_num_primary_experts`` counts the experts HELD here,
``first_expert_held`` the first of them,
``moe_num_primary_experts_published`` the router's width (None: all are
held); ``vocab_size`` is the slice of the vocabulary held here. The two
lists may be the published 52 entries: the first ``num_hidden_layers``
are read.

Under ``amp.auto_cast`` the residual stream is in the compute dtype;
router, rotary angles, every norm's statistics and the loss stay float32.
``recompute`` checkpoints each block (``jit.recompute``). The cache of
serving (a window layer's keys evicted at ``i - W``) is not here.
"""
from __future__ import annotations

from .. import amp, nn, ops
from .. import initializer as I
from ..ops import manip

_PERIOD = (0, 1, 1, 1)


def _layout(name, given, layers):
    """A per-layer list of 0 / 1 as a tuple of the first ``layers``
    entries; None is the published period."""
    if given is None:
        given = [_PERIOD[i % 4] for i in range(layers)]
    if len(given) < layers or any(x not in (0, 1) for x in given):
        raise ValueError(f"{name} gives no 0 or 1 for each of the {layers} "
                         f"layers: {given!r}")
    return tuple(given[:layers])


class SmallThinkerConfig:
    """The published keys (defaults: SmallThinker-21BA3B-Instruct) and
    what says which share of the model this is."""

    def __init__(self, vocab_size=151936, hidden_size=2560,
                 moe_ffn_hidden_size=768, num_hidden_layers=52,
                 num_attention_heads=28, num_key_value_heads=4, head_dim=128,
                 rope_theta=1500000.0, rope_layout=None,
                 sliding_window_layout=None, sliding_window_size=4096,
                 moe_num_primary_experts=64,
                 moe_num_active_primary_experts=6,
                 moe_primary_router_apply_softmax=True, norm_topk_prob=True,
                 rms_norm_eps=1e-6, initializer_range=0.02,
                 moe_num_primary_experts_published=None,
                 first_expert_held=0, recompute=False):
        if moe_num_primary_experts_published is None:
            moe_num_primary_experts_published = moe_num_primary_experts
        if first_expert_held < 0 or moe_num_primary_experts < 1 or \
                first_expert_held + moe_num_primary_experts \
                > moe_num_primary_experts_published:
            raise ValueError(
                f"experts {first_expert_held} .. {first_expert_held} + "
                f"{moe_num_primary_experts} are not a range of the "
                f"{moe_num_primary_experts_published} published")
        if not (norm_topk_prob and moe_primary_router_apply_softmax):
            raise ValueError(
                "only the source's router is written: a soft-max over all "
                "experts, top-k, renormalised over the chosen")
        rope_layout = _layout("rope_layout", rope_layout,
                              num_hidden_layers)
        sliding_window_layout = _layout(
            "sliding_window_layout", sliding_window_layout, num_hidden_layers)
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != "self"})

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=256, hidden_size=64, moe_ffn_hidden_size=32,
                 num_hidden_layers=4, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
                 sliding_window_size=8, moe_num_primary_experts=4,
                 moe_num_primary_experts_published=16,
                 moe_num_active_primary_experts=3)
        d.update(kw)
        return SmallThinkerConfig(**d)


class SmallThinkerBlock(nn.Layer):
    def __init__(self, config, layer):
        super().__init__()
        c = config
        windowed = c.sliding_window_layout[layer]
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = nn.GroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, causal=True,
            rope_theta=c.rope_theta if c.rope_layout[layer] else None,
            window=c.sliding_window_size if windowed else None)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.block_sparse_moe = nn.RoutedMoE(
            c.hidden_size, c.moe_ffn_hidden_size,
            c.moe_num_primary_experts_published,
            c.moe_num_active_primary_experts,
            experts_held=range(
                c.first_expert_held,
                c.first_expert_held + c.moe_num_primary_experts),
            gated=True, scoring="softmax", activation="relu")

    def forward(self, x):
        h = x + self.self_attn(self.input_layernorm(x))
        return h + self.block_sparse_moe(self.post_attention_layernorm(h),
                                         router_input=x)


class SmallThinkerForCausalLM(nn.Layer):
    def __init__(self, config: SmallThinkerConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [SmallThinkerBlock(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
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
        return self.lm_head(self.norm(h))

    def loss(self, logits, input_ids):
        """Mean next-token cross entropy over the predicted positions:
        position t's logits against token t + 1. The labels are shifted
        and the last position's is the loss's ``ignore_index``, so that
        the logits stay whole: a slice to ``T - 1`` rows would copy 1.2 GB
        of float32 logits at 16,384 x 18,992, and their gradient back."""
        b = input_ids.shape[0]
        labels = manip.concat(
            [input_ids[:, 1:], ops.full([b, 1], -100, dtype=input_ids.dtype)],
            axis=1)
        return ops.loss.cross_entropy(logits, labels, ignore_index=-100)
