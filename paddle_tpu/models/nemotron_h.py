"""NemotronH: a hybrid causal language model whose blocks are Mamba-2
mixers, grouped-query attention and routed mixtures of experts, in the
order a pattern string gives (``model_type: nemotron_h``; the published
``config.json`` of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 names the code).

No reference counterpart in Paddle Fluid 1.7. Every block is pre-norm on
one residual stream, ``h <- h + Mixer(RMSNorm(h))``, with one mixer a
block: ``M`` :class:`nn.Mamba2Mixer`, ``*`` :class:`nn.
GroupedQueryAttention` (causal, no rotary embedding: the ``nemotron_h``
attention applies none), ``E`` :class:`nn.RoutedMoE`. After the last
block ``logits = RMSNorm_f(h) W_head``, the head untied from the
embedding. Parameter names follow the source's state dict
(``layers.<i>.norm.weight``, ``layers.<i>.mixer.<...>``).

**A chip's share.** ``n_routed_experts`` counts the experts HELD here,
``first_expert_held`` the first of them, and ``n_routed_experts_published``
the router's width (None: all are held, the whole model). ``vocab_size``
is the slice of the vocabulary held here. The model computes its share's
part of each expert layer and goes on with that partial result (guide
``model-configs`` section 4; PERF.md section 4).

Under ``amp.auto_cast`` the residual stream is in the compute dtype;
router, decays and every norm's statistics stay float32. ``recompute``
checkpoints each block (``jit.recompute``): a block's activations are
made again in the backward pass.
"""
from __future__ import annotations

import math

from .. import amp, nn, ops
from .. import initializer as I

KINDS = ("M", "*", "E")


class NemotronHConfig:
    """The published keys (defaults: Nemotron-3-Nano-30B-A3B), plus what
    says which share of the model this is."""

    def __init__(self, vocab_size=131072, hidden_size=2688,
                 num_hidden_layers=52,
                 hybrid_override_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*"
                                         "EMEMEMEM*EMEMEMEME",
                 mamba_num_heads=64, mamba_head_dim=64, ssm_state_size=128,
                 n_groups=8, conv_kernel=4, chunk_size=128,
                 time_step_min=0.001, time_step_max=0.1,
                 time_step_floor=1e-4,
                 num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                 n_routed_experts=128, num_experts_per_tok=6,
                 moe_intermediate_size=1856,
                 moe_shared_expert_intermediate_size=3712,
                 routed_scaling_factor=2.5, layer_norm_epsilon=1e-5,
                 initializer_range=0.02, rescale_prenorm_residual=True,
                 n_routed_experts_published=None, first_expert_held=0,
                 recompute=False):
        if len(hybrid_override_pattern) != num_hidden_layers or \
                set(hybrid_override_pattern) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern {hybrid_override_pattern!r} does "
                f"not describe {num_hidden_layers} layers of {KINDS}")
        self.__dict__.update(
            {k: v for k, v in locals().items() if k != "self"})
        if n_routed_experts_published is None:
            self.n_routed_experts_published = n_routed_experts

    @staticmethod
    def tiny(**kw):
        d = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4,
                 hybrid_override_pattern="ME*E", mamba_num_heads=4,
                 mamba_head_dim=16, ssm_state_size=16, n_groups=2,
                 chunk_size=8, num_attention_heads=4, num_key_value_heads=2,
                 head_dim=16, n_routed_experts=4,
                 n_routed_experts_published=16, num_experts_per_tok=3,
                 moe_intermediate_size=32,
                 moe_shared_expert_intermediate_size=64)
        d.update(kw)
        return NemotronHConfig(**d)


def _mixer(config, kind):
    c = config
    if kind == "M":
        return nn.Mamba2Mixer(
            c.hidden_size, c.mamba_num_heads, c.mamba_head_dim,
            c.ssm_state_size, n_groups=c.n_groups,
            conv_kernel=c.conv_kernel, chunk_size=c.chunk_size,
            epsilon=c.layer_norm_epsilon, time_step_min=c.time_step_min,
            time_step_max=c.time_step_max,
            time_step_floor=c.time_step_floor)
    if kind == "*":
        return nn.GroupedQueryAttention(
            c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, causal=True)
    return nn.RoutedMoE(
        c.hidden_size, c.moe_intermediate_size,
        c.n_routed_experts_published, c.num_experts_per_tok,
        d_shared=c.moe_shared_expert_intermediate_size,
        experts_held=range(c.first_expert_held,
                           c.first_expert_held + c.n_routed_experts),
        routed_scaling_factor=c.routed_scaling_factor)


class NemotronHBlock(nn.Layer):
    def __init__(self, config, kind):
        super().__init__()
        self.kind = kind
        self.norm = nn.RMSNorm(config.hidden_size, config.layer_norm_epsilon)
        self.mixer = _mixer(config, kind)

    def forward(self, h):
        return h + self.mixer(self.norm(h))


class NemotronHForCausalLM(nn.Layer):
    def __init__(self, config: NemotronHConfig):
        super().__init__()
        self.config = config
        std = config.initializer_range
        self.embeddings = nn.Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=I.Normal(0.0, std))
        self.layers = nn.LayerList(
            [NemotronHBlock(config, kind)
             for kind in config.hybrid_override_pattern])
        self.norm_f = nn.RMSNorm(config.hidden_size,
                                 config.layer_norm_epsilon)
        self.lm_head = nn.Linear(config.hidden_size, config.vocab_size,
                                 bias_attr=False)
        self._init_weights()

    def _init_weights(self):
        """Matrices normal(0, initializer_range); the projections that
        write to the residual stream divided by sqrt(depth)
        (``rescale_prenorm_residual``)."""
        c = self.config
        init = I.Normal(0.0, c.initializer_range)
        out_scale = 1.0 / math.sqrt(c.num_hidden_layers) \
            if c.rescale_prenorm_residual else 1.0
        writes = ("out_proj.weight", "o_proj.weight", "experts_down",
                  "shared_down.weight")
        for name, p in self.named_parameters():
            if len(p.shape) < 2 or name.endswith("conv_weight"):
                continue
            value = init(tuple(p.shape), "float32")
            p.set_value(value * out_scale if name.endswith(writes)
                        else value)

    def forward(self, input_ids):
        h = self.embeddings(input_ids)
        if amp.is_enabled():
            h = h.astype(amp.compute_dtype())
        if self.config.recompute:
            from .. import jit
            for block in self.layers:
                h = jit.recompute(block, h)
        else:
            for block in self.layers:
                h = block(h)
        return self.lm_head(self.norm_f(h))

    def loss(self, logits, input_ids):
        """Mean next-token cross entropy over the predicted positions:
        position t's logits against token t + 1."""
        s = input_ids.shape[1]
        return ops.loss.cross_entropy(logits[:, :s - 1], input_ids[:, 1:])
