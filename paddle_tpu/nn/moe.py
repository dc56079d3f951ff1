"""Mixture-of-Experts FFN — expert parallelism for the user Layer stack.

TPU-native design (no reference counterpart in Paddle Fluid 1.7 — the ep
axis is part of this framework's 5-axis scale-out story, matching the
manual-collective MoE in parallel/megatron.py): the GShard/Mesh-TF dense
dispatch formulation. Expert weights are STACKED on a leading [E] axis; a
top-1 gate builds a dispatch one-hot [T, E, C] (T tokens, C capacity per
expert) and the whole layer is four einsums. Under `fleet.distributed_model`
the expert axis is sharded over the mesh's `ep` axis (see
fleet.megatron_param_spec), and GSPMD lowers the dispatch/combine einsums
into the token all-to-all the megatron trainer writes by hand — static
shapes, MXU-friendly, no data-dependent control flow.

Load balancing: the standard GShard auxiliary loss E·Σ_e(mean_gate_e ·
frac_tokens_e) is computed every forward and stashed on the layer as
``self.aux_loss`` (a live Tensor on the autograd tape); training code adds
``moe_aux_loss(model)`` to its objective to activate it.
"""
from __future__ import annotations

import numpy as np

from .layer import Layer
from ..tensor import Tensor
from ..dispatch import apply
from .. import initializer as I

__all__ = ["MoEFFN", "RoutedMoE", "GatedMLP", "moe_aux_loss"]


class MoEFFN(Layer):
    """Drop-in replacement for the Linear–act–Linear FFN block.

    d_model -> [num_experts] x (d_model -> d_ffn -> d_model), top-1 gated,
    capacity = ceil(T / E * capacity_factor) tokens per expert (overflow
    tokens pass through the residual untouched, GShard semantics).
    """

    def __init__(self, d_model, d_ffn, num_experts, capacity_factor=1.25,
                 activation="gelu"):
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = float(capacity_factor)
        self.activation = activation
        self.gate_w = self.create_parameter(
            (d_model, num_experts),
            default_initializer=I.Normal(0.0, 0.02))
        k = 1.0 / np.sqrt(d_model)
        # expert-stacked: leading axis is the EXPERT axis (sharded over ep
        # by fleet.megatron_param_spec's "experts_" rule)
        self.experts_w1 = self.create_parameter(
            (num_experts, d_model, d_ffn),
            default_initializer=I.Uniform(-k, k))
        self.experts_b1 = self.create_parameter(
            (num_experts, d_ffn), is_bias=True)
        kf = 1.0 / np.sqrt(d_ffn)
        self.experts_w2 = self.create_parameter(
            (num_experts, d_ffn, d_model),
            default_initializer=I.Uniform(-kf, kf))
        self.experts_b2 = self.create_parameter(
            (num_experts, d_model), is_bias=True)
        self.aux_loss = None

    def forward(self, x):
        import jax
        import jax.numpy as jnp
        E = self.num_experts
        act_name = self.activation

        def impl(x, gate_w, w1, b1, w2, b2):
            lead = x.shape[:-1]
            d = x.shape[-1]
            tokens = x.reshape(-1, d)
            T = tokens.shape[0]
            C = max(1, int(np.ceil(T / E * self.capacity_factor)))

            logits = tokens @ gate_w                     # [T, E]
            probs = jax.nn.softmax(logits, axis=-1)
            expert = jnp.argmax(probs, axis=-1)          # [T]
            gate = jnp.max(probs, axis=-1)               # [T]

            onehot = jax.nn.one_hot(expert, E, dtype=jnp.float32)
            # position of each token within its expert's capacity bucket
            pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # [T, E]
            keep = (pos < C) & (onehot > 0)
            pos_c = jax.nn.one_hot(jnp.sum(pos, axis=-1).astype(jnp.int32),
                                   C, dtype=jnp.float32)         # [T, C]
            dispatch = keep.astype(jnp.float32)[:, :, None] * \
                pos_c[:, None, :]                                # [T, E, C]

            expert_in = jnp.einsum("tec,td->ecd", dispatch,
                                   tokens.astype(jnp.float32))
            h = jnp.einsum("ecd,edf->ecf", expert_in, w1) + b1[:, None, :]
            h = getattr(jax.nn, act_name)(h)
            out = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]
            combine = dispatch * gate[:, None, None]             # [T, E, C]
            y = jnp.einsum("tec,ecd->td", combine, out)
            y = y.astype(x.dtype).reshape(*lead, d)

            # GShard load-balance aux: E * sum_e mean_t(prob_e)*frac_e
            frac = jnp.mean(onehot, axis=0)
            mean_prob = jnp.mean(probs, axis=0)
            aux = E * jnp.sum(frac * mean_prob)
            return y, aux

        y, aux = apply(impl, (x, self.gate_w, self.experts_w1,
                              self.experts_b1, self.experts_w2,
                              self.experts_b2), name="moe_ffn", n_out=2)
        self.aux_loss = aux
        return y


class GatedMLP(Layer):
    """The gated feed-forward block of the Llama / DeepSeek families
    (SwiGLU; Shazeer, arXiv:2002.05202): ``W_down(silu(W_gate x) * W_up
    x)``, no bias. Parameter names are the families' (``gate_proj``,
    ``up_proj``, ``down_proj``)."""

    def __init__(self, d_model, d_ffn):
        super().__init__()
        from .layers import Linear
        self.gate_proj = Linear(d_model, d_ffn, bias_attr=False)
        self.up_proj = Linear(d_model, d_ffn, bias_attr=False)
        self.down_proj = Linear(d_ffn, d_model, bias_attr=False)

    def forward(self, x):
        from ..ops import nn_ops as F
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class RoutedMoE(Layer):
    """The local half of an expert-parallel mixture-of-experts layer.
    The router is DeepSeek-V3's (``scoring="sigmoid"``: sigmoid scores, a
    selection bias, top-k weights renormalised and scaled) or Qwen3-MoE's
    (``scoring="softmax"``: a soft-max over all experts, top-k,
    renormalised over the chosen; no bias buffer); ``d_shared`` adds one
    always-on shared expert.
    The experts are ``W_down relu(W_up x)^2`` (``nemotron_h``), or with
    ``gated=True`` ``W_down(silu(W_gate x) * W_up x)`` (DeepSeek-V3,
    ``joyai_llm_flash``): ``experts_gate`` beside ``experts_up``, and the
    shared expert a :class:`GatedMLP` named ``shared_experts`` in place of
    ``shared_up`` / ``shared_down``; ``activation="relu"`` gates with
    ``relu(W_gate x)`` instead (``smallthinker``). ``forward(u,
    router_input=t)`` routes by ``t`` and feeds the experts ``u`` (a
    router placed ahead of attention reads the block's input, the experts
    what attention left). The layer is TOLD which experts
    it holds (``experts_held``, a range over the model's ``num_experts``),
    routes over all of them, and returns what its own experts give for
    the tokens routed to them, plus the shared expert:

        y = sum_{i in chosen & held} w_i Expert_i(u) + Expert_shared(u)

    What the experts on other chips would add is left out, and nothing
    here stands in for them or for their exchange. Dropless under any
    imbalance (``F.moe_experts``). With ``experts_held=None`` the layer
    holds every expert and is the whole layer.

    ``stats`` (a buffer, int32[5], ``ops.moe.MOE_STATS``) adds up what
    each call routed, on the device; ``monitor.device_counters.read()``
    gives it as ``moe.slots_routed_here``, ``moe.slots_dropped``,
    ``moe.expert_load_max`` (the fullest expert's rows, summed over
    calls), ``moe.steps`` (calls, every layer counted) and
    ``moe.rows_computed`` (the rows the experts' products ran over,
    padding included: what the layer's time follows).

    Beside :class:`MoEFFN` (top-1, capacity-bounded, dense dispatch),
    which stays as it is."""

    COUNTERS = ("moe.slots_routed_here", "moe.slots_dropped",
                "moe.expert_load_max", "moe.steps", "moe.rows_computed")

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 d_shared=None, experts_held=None, routed_scaling_factor=1.0,
                 gated=False, scoring="sigmoid", activation="silu"):
        super().__init__()
        import jax.numpy as jnp
        from .layers import Linear
        from .. import monitor
        held = range(num_experts) if experts_held is None else experts_held
        if held.step != 1 or held.start < 0 or held.stop > num_experts \
                or not len(held):
            raise ValueError(f"RoutedMoE: experts_held {held!r} is not a "
                             f"range of the {num_experts} experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.experts_held = held
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.scoring = scoring
        if activation != "silu" and (not gated or d_shared):
            raise ValueError(f"RoutedMoE: activation {activation!r} is the "
                             f"gate of gated experts with no shared expert")
        self.activation = activation
        self.router = Linear(d_model, num_experts, bias_attr=False,
                             weight_attr=I.Normal(0.0, 0.02))
        # the sigmoid router's selection bias: moved by a balancing rule
        # outside the gradient (none here), so a buffer and not a parameter
        if scoring == "sigmoid":
            self.register_buffer("e_score_correction_bias", Tensor(
                jnp.zeros((num_experts,), jnp.float32)))
        else:
            self.e_score_correction_bias = None
        self.experts_gate = None
        if gated:
            self.experts_gate = self.create_parameter(
                (len(held), d_model, d_expert),
                default_initializer=I.Normal(0.0, 0.02))
        self.experts_up = self.create_parameter(
            (len(held), d_model, d_expert),
            default_initializer=I.Normal(0.0, 0.02))
        self.experts_down = self.create_parameter(
            (len(held), d_expert, d_model),
            default_initializer=I.Normal(0.0, 0.02))
        self.shared_up = self.shared_down = self.shared_experts = None
        if d_shared and gated:
            self.shared_experts = GatedMLP(d_model, d_shared)
        elif d_shared:
            self.shared_up = Linear(d_model, d_shared, bias_attr=False)
            self.shared_down = Linear(d_shared, d_model, bias_attr=False)
        self.register_buffer("stats", monitor.device_counters.register(
            self.COUNTERS, Tensor(jnp.zeros((len(self.COUNTERS),),
                                            jnp.int32)), owner=self),
            persistable=False)

    def forward(self, u, router_input=None):
        from ..ops import moe as M
        from ..ops import nn_ops as F
        weights, experts = M.moe_route(
            u if router_input is None else router_input,
            self.router.weight, self.e_score_correction_bias,
            top_k=self.top_k, scale=self.routed_scaling_factor,
            scoring=self.scoring)
        y, seen = M.moe_experts(u, experts, weights, self.experts_up,
                                self.experts_down,
                                first_expert=self.experts_held.start,
                                w_gate=self.experts_gate,
                                activation=self.activation)
        self.stats.data = self.stats.data + seen.data
        if self.shared_experts is not None:
            y = y + self.shared_experts(u)
        elif self.shared_up is not None:
            h = F.relu(self.shared_up(u))
            y = y + self.shared_down(h * h)
        return y


def moe_aux_loss(model, weight=0.01):
    """Sum the aux_loss of every MoE-bearing layer in `model`, scaled by
    `weight` (call AFTER the forward pass; returns 0.0 if the model has no
    MoE). Any sublayer exposing a non-None ``aux_loss`` Tensor counts —
    MoEFFN itself, and aggregators like parallel.pipeline.PipelineStack
    which total the aux of MoE blocks hidden inside their scan."""
    total = None
    for layer in model.sublayers(include_self=True):
        aux = getattr(layer, "aux_loss", None)
        if aux is not None:
            total = aux if total is None else total + aux
    if total is None:
        return 0.0
    return total * weight
