"""The language model of Keye-VL-2.0 (``model_type: KeyeVL2``; the published
``config.json`` of Kwai-Keye/Keye-VL-2.0-30B-A3B names the sizes): a
Qwen3-MoE-shaped decoder whose attention reads a LEARNED SELECTION of keys
(``sa_config``: a 16-head indexer of 64, one key head, the ``topk`` best
keys a row; DeepSeek sparse attention, DeepSeek-V3.2-Exp's report section
2, in front of grouped-query attention) under rotary positions of three
axes (``rope_scaling.mrope_section``: temporal, height, width). The vision
tower is not here: what it leaves in the language model is the three rows
of position ids over an image's span, which the batch carries.

No reference counterpart in Paddle Fluid 1.7. Every block is pre-norm on
one residual stream: ``h <- h + Attn(RMSNorm(h))``, then ``h <- h +
MoE(RMSNorm(h))``. ``Attn`` is :class:`nn.SparseGroupedQueryAttention`
(head norms, the half-split rotation with each pair's angle from its
section's axis, the indexer, the selection, the flash kernels under it);
``MoE`` a gated :class:`nn.RoutedMoE` with the soft-max router and no
shared expert, the ``sdar_moe`` layer. The head is untied.

**Two losses in one step** (the sparse stage of that report's continued
training): every layer's attention returns the indexer's own loss ``L_I``
beside its output; ``forward(ids, position_ids)`` returns ``(logits, sum
of the layers' L_I)`` and ``loss(logits, ids, weights, indexer_loss)`` is
``L_LM + sum L_I``, with ``L_LM = sum_t u_t CE(logits_t, id_{t+1}) /
sum_t u_t`` (``u`` the batch's label weights: 0 where the next position
lies in an image's span, which predicts nothing). Their gradients are
disjoint: ``L_I`` reaches the indexers' parameters alone and ``L_LM``
none of them.

``position_ids`` is ``[3, S]``: the sequences of a batch share one layout.

Parameter names follow the ``qwen3_moe`` state dict without its ``model.``
prefix, as ``models/sdar_moe.py`` (``mlp.router.weight``, the experts
stacked ``[held, in, out]``); the indexer's are ``self_attn.indexer_q /
indexer_k / indexer_k_norm / indexer_w``. **A chip's share** as there:
``num_experts`` counts the experts HELD here, ``first_expert_held`` the
first of them, ``num_experts_published`` the router's width (None: all are
held); ``vocab_size`` is the slice of the vocabulary held here.

Under ``amp.auto_cast`` the residual stream is in the compute dtype;
router, rotary angles, every norm's statistics, the index scores, the
threshold, both losses stay float32. ``recompute`` checkpoints each block
(``jit.recompute``).
"""
from __future__ import annotations

from .. import amp, nn, ops
from .. import initializer as I
from ..ops import manip

_ROPE_SCALING = dict(mrope_section=[16, 24, 24], rope_type="default",
                     type="default")
_SA_CONFIG = dict(indexer_num_heads=16, indexer_head_dim=64,
                  indexer_num_kv_heads=1, topk=2048, q_chunk_size=512,
                  kv_chunk_size=512)


class KeyeVL2TextConfig:
    """The published keys of the language model (defaults:
    Keye-VL-2.0-30B-A3B), ``sa_config`` whole, and what says which share
    of the model this is."""

    def __init__(self, vocab_size=151936, hidden_size=2048,
                 moe_intermediate_size=768, num_hidden_layers=48,
                 num_attention_heads=32, num_key_value_heads=4, head_dim=128,
                 rope_theta=10000000.0, rope_scaling=None, num_experts=128,
                 num_experts_per_tok=8, norm_topk_prob=True, rms_norm_eps=1e-6, initializer_range=0.02, sa_config=None,
                 num_experts_published=None, first_expert_held=0,
                 recompute=False):
        sa_config = dict(_SA_CONFIG if sa_config is None else sa_config)
        rope_scaling = dict(_ROPE_SCALING if rope_scaling is None
                            else rope_scaling)
        mrope_section = tuple(rope_scaling.get("mrope_section", ()))
        if num_experts_published is None:
            num_experts_published = num_experts
        if first_expert_held < 0 or num_experts < 1 or \
                first_expert_held + num_experts > num_experts_published:
            raise ValueError(
                f"experts {first_expert_held} .. {first_expert_held} + "
                f"{num_experts} are not a range of the "
                f"{num_experts_published} published")
        if not norm_topk_prob:
            raise ValueError("norm_topk_prob false: only the source's "
                             "renormalised top-k weights are written")
        if len(mrope_section) != 3 or sum(mrope_section) != head_dim // 2:
            raise ValueError(
                f"rope_scaling.mrope_section {mrope_section}: three chunks "
                f"that add up to the {head_dim // 2} pairs of a head")
        if set(sa_config) != set(_SA_CONFIG) \
                or sa_config["indexer_num_kv_heads"] != 1:
            raise ValueError(f"sa_config {sa_config}: the keys "
                             f"{sorted(_SA_CONFIG)} with one indexer key "
                             f"head are what is written")
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != "self"})

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, rope_theta=10000.0,
                 rope_scaling=dict(_ROPE_SCALING, mrope_section=[2, 3, 3]),
                 num_experts=4, num_experts_published=16,
                 num_experts_per_tok=3,
                 sa_config=dict(_SA_CONFIG, indexer_num_heads=4,
                                indexer_head_dim=8, topk=8))
        d.update(kw)
        return KeyeVL2TextConfig(**d)


class KeyeVL2TextBlock(nn.Layer):
    def __init__(self, config):
        super().__init__()
        c, sa = config, config.sa_config
        self.input_layernorm = nn.RMSNorm(c.hidden_size, c.rms_norm_eps)
        self.self_attn = nn.SparseGroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, sa["indexer_num_heads"], sa["indexer_head_dim"],
            sa["topk"], qk_norm_epsilon=c.rms_norm_eps,
            rope_theta=c.rope_theta, rope_sections=c.mrope_section,
            indexer_epsilon=c.rms_norm_eps)
        self.post_attention_layernorm = nn.RMSNorm(c.hidden_size,
                                                   c.rms_norm_eps)
        self.mlp = nn.RoutedMoE(
            c.hidden_size, c.moe_intermediate_size, c.num_experts_published,
            c.num_experts_per_tok,
            experts_held=range(c.first_expert_held,
                               c.first_expert_held + c.num_experts),
            gated=True, scoring="softmax")

    def forward(self, h, position_ids):
        """``(h, the layer's indexer loss)``."""
        a, indexer_loss = self.self_attn(self.input_layernorm(h),
                                         positions=position_ids)
        h = h + a
        return h + self.mlp(self.post_attention_layernorm(h)), indexer_loss


class KeyeVL2ForCausalLM(nn.Layer):
    def __init__(self, config: KeyeVL2TextConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [KeyeVL2TextBlock(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        # every matrix normal(0, initializer_range), norm scales 1 and the
        # indexer's key norm (1, 0)
        init = I.Normal(0.0, config.initializer_range)
        for _, p in self.named_parameters():
            if len(p.shape) >= 2:
                p.set_value(init(tuple(p.shape), "float32"))

    def forward(self, input_ids, position_ids):
        """``(logits [B, S, vocab], the layers' indexer losses added
        up)``; ``position_ids`` int32 ``[3, S]``."""
        from .. import jit
        h = self.embed_tokens(input_ids)
        if amp.is_enabled():
            h = h.astype(amp.compute_dtype())
        indexer_loss = None
        for block in self.layers:
            h, part = jit.recompute(block, h, position_ids) \
                if self.config.recompute else block(h, position_ids)
            indexer_loss = part if indexer_loss is None \
                else indexer_loss + part
        return self.lm_head(self.norm(h)), indexer_loss

    def loss(self, logits, input_ids, label_weights, indexer_loss):
        """``L_LM + sum L_I``: position t's logits against token t + 1
        under ``label_weights[t]``, normalised by the weights' sum (a
        sequence's last position has no next token: its weight has to be
        0), plus the indexers' losses at coefficient 1."""
        b, s = input_ids.shape[0], input_ids.shape[1]
        labels = manip.concat(
            [input_ids[:, 1:], ops.zeros([b, 1], dtype=input_ids.dtype)],
            axis=1)
        u = label_weights.astype("float32")
        lm = ops.loss.block_diffusion_loss(
            logits, labels, u * (float(b * s) / ops.sum(u)))
        return lm + indexer_loss
