"""Phi-4-mini-flash: a decoder-hybrid-decoder causal language model
(``model_type: phi4flash``; the published ``config.json`` of
microsoft/Phi-4-mini-flash-reasoning names the sizes, "Decoder-Hybrid-
Decoder Architecture for Efficient Reasoning with Long Generation",
arXiv:2507.06607 — SambaY with differential attention — and the model code
published beside the config the layers).

No reference counterpart in Paddle Fluid 1.7. Every block is pre-norm on
one residual stream, with layer norms that carry a scale AND a bias:

    h = x + Mixer_l(LN(x))          y = h + MLP(LN(h))

``MLP`` is a :class:`nn.GatedMLP` of ``intermediate_size`` without bias.
There is NO position embedding anywhere. The stack is not a period but two
decoders, ``N = num_hidden_layers`` as published, by the SOURCE index
``l`` (``layer_plan`` holds one of the five kinds for each published
layer):

``mamba``            (``l`` even, ``l <= N/2``) :class:`nn.MambaMixer`,
                     Mamba-1. **Layer N/2 also hands on its scan result,
                     taken before the gate: the memory.**
``window_attention`` (``l`` odd, ``l < N/2``) :class:`nn.DifferentialAttention`
                     under a sliding window of ``sliding_window``.
``full_attention``   (``l = N/2 + 1``) the same, full causal. **It also
                     hands on its keys and values.**
``memory_unit``      (``l`` even, ``l >= N/2 + 2``)
                     :class:`nn.GatedMemoryUnit` over layer N/2's memory.
``cross_attention``  (``l`` odd, ``l >= N/2 + 3``) queries only,
                     differential attention over layer N/2 + 1's keys and
                     values, full causal.

So the second decoder has no token mixer of its own: it reads ONE layer's
memory and ONE layer's keys and values, and those two tensors get their
gradients as sums over their readers (the tape adds them; under
``recompute`` a block's extra results and extra arguments are checkpoint
outputs and inputs, ``jit.recompute``). Then a final layer norm and the
head, which IS the embedding: ``logits = h E^T``, no bias. Loss: the mean
next-token cross entropy.

Parameter names follow the source's state dict without its ``model.``
prefix where the layers here have the source's parts (``embed_tokens``,
``final_layernorm``, ``layers.<i>.input_layernorm / post_attention_
layernorm``); a mixer is ``layers.<i>.mixer`` with :mod:`nn.hybrid`'s names
(``in_proj / conv_weight / conv_bias / x_proj / dt_proj / A_log / D /
out_proj``; ``q_proj / k_proj / v_proj`` for the source's fused ``Wqkv``,
``o_proj``, ``lambda_q1 .. lambda_k2``, ``subln``), the feed-forward
``layers.<i>.mlp`` with ``nn.GatedMLP``'s (``gate_proj | up_proj`` for
the fused ``fc1``, ``down_proj``).

**A chip's share**: ``vocab_size`` is the slice of the vocabulary held
here; ``num_hidden_layers`` the layers held, the source's ``first_layer
.. first_layer + num_hidden_layers`` of ``num_hidden_layers_published``:
the boundary ``N/2``, the windowed layers and ``lambda_init`` follow the
SOURCE indices. A held reader needs its giver held too.

Under ``amp.auto_cast`` the residual stream is in the compute dtype; the
scan (state, step sizes, exponentials, gate), ``lambda``, the pair norm
and the subtraction of the two attention maps, every layer norm's
statistics, the soft-max statistics and the loss stay float32.
``recompute`` checkpoints each block. The serving side (a scan state and
ONE layer's keys and values shared by every cross layer in the cache) is
not here.
"""
from __future__ import annotations

from .. import amp, monitor, nn, ops
from .. import initializer as I
from ..ops import manip

KINDS = ("mamba", "window_attention", "full_attention", "memory_unit",
         "cross_attention")


def published_plan(layers=32, mb_per_layer=2):
    """The kind of each published layer: a self-decoder of Mamba and
    windowed attention in turn up to the boundary ``layers / 2`` (a Mamba
    layer, which gives the memory), one full attention layer (which gives
    keys and values), then memory units and cross attention in turn."""
    half = layers // 2
    plan = []
    for l in range(layers):
        if l % mb_per_layer == 0:
            plan.append("mamba" if l <= half else "memory_unit")
        elif l < half:
            plan.append("window_attention")
        else:
            plan.append("full_attention" if l == half + 1
                        else "cross_attention")
    return tuple(plan)


def _giver(plan, kind, reader):
    """The layer that hands on what ``reader`` layers read: the last
    ``kind`` layer in front of the first reader; None without a reader."""
    if reader not in plan:
        return None
    first = plan.index(reader)
    found = [l for l in range(first) if plan[l] == kind]
    if not found:
        raise ValueError(f"layer_plan has a {reader} at {first} and no "
                         f"{kind} in front of it: {plan!r}")
    return found[-1]


class Phi4FlashConfig:
    """The published keys (defaults: Phi-4-mini-flash-reasoning), the
    Mamba sizes of its model code, and what says which share of the model
    this is."""

    def __init__(self, vocab_size=200064, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=32,
                 num_attention_heads=40, num_key_value_heads=20,
                 sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5,
                 mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
                 mamba_dt_rank=None, layer_plan=None, first_layer=0,
                 num_hidden_layers_published=None, initializer_range=0.02,
                 lambda_std=0.1, recompute=False):
        if num_hidden_layers_published is None:
            num_hidden_layers_published = first_layer + num_hidden_layers
        given = published_plan(num_hidden_layers_published, mb_per_layer) \
            if layer_plan is None else tuple(layer_plan)
        held = range(first_layer, first_layer + num_hidden_layers)
        if first_layer < 0 or held.stop > len(given) \
                or set(given) - set(KINDS):
            raise ValueError(
                f"layer_plan gives no kind of {KINDS} for each of the "
                f"{num_hidden_layers} layers from {first_layer} on: "
                f"{given!r}")
        # the two hand-overs, by source index
        memory_layer = _giver(given, "mamba", "memory_unit")
        kv_layer = _giver(given, "full_attention", "cross_attention")
        for giver, reader in ((memory_layer, "memory_unit"),
                              (kv_layer, "cross_attention")):
            if any(given[l] == reader for l in held) and giver not in held:
                raise ValueError(
                    f"a held {reader} reads layer {giver}, which is not "
                    f"among the held layers {held.start}..{held.stop - 1}")
        if hidden_size % num_attention_heads:
            raise ValueError(f"{num_attention_heads} heads do not divide "
                             f"{hidden_size}")
        head_dim = hidden_size // num_attention_heads
        mamba_d_inner = mamba_expand * hidden_size
        layer_plan = given
        self.__dict__.update(
            {k: v for k, v in locals().items()
             if k not in ("self", "given", "held", "giver", "reader")})

    def kind(self, i):
        """The kind of held layer ``i``."""
        return self.layer_plan[self.first_layer + i]

    @staticmethod
    def tiny(**kw):
        """The cut of the benchmark's configuration at a toy width: the
        six source layers 14..19 of 32, every kind and both hand-overs."""
        d = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                 num_hidden_layers=6, first_layer=14,
                 num_hidden_layers_published=32, num_attention_heads=4,
                 num_key_value_heads=2, sliding_window=8, mamba_d_state=4)
        d.update(kw)
        return Phi4FlashConfig(**d)


class Phi4FlashBlock(nn.Layer):
    """One block; ``forward(x, *handed)`` takes what its mixer reads of
    another block (the memory, or keys and values) and returns ``x`` alone
    or, from a giver, ``(x, memory)`` / ``(x, k, v)``."""

    def __init__(self, config, layer):
        super().__init__()
        c, source = config, config.first_layer + layer
        self.kind = c.kind(layer)
        self.gives = source in (c.memory_layer, c.kv_layer)
        self.input_layernorm = nn.LayerNorm(c.hidden_size, c.layer_norm_eps)
        if self.kind == "mamba":
            self.mixer = nn.MambaMixer(
                c.hidden_size, c.mamba_d_inner, c.mamba_d_state,
                c.mamba_d_conv, c.mamba_dt_rank)
        elif self.kind == "memory_unit":
            self.mixer = nn.GatedMemoryUnit(c.hidden_size, c.mamba_d_inner)
        else:
            self.mixer = nn.DifferentialAttention(
                c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
                c.head_dim, depth=source, epsilon=c.layer_norm_eps,
                window=c.sliding_window
                if self.kind == "window_attention" else None,
                cross=self.kind == "cross_attention")
        self.post_attention_layernorm = nn.LayerNorm(c.hidden_size,
                                                     c.layer_norm_eps)
        self.mlp = nn.GatedMLP(c.hidden_size, c.intermediate_size)

    def forward(self, x, *handed):
        n = self.input_layernorm(x)
        extra = ()
        if self.kind == "memory_unit":
            out = self.mixer(n, handed[0])
        elif self.kind == "cross_attention":
            out = self.mixer(n, kv=handed)
        elif not self.gives:
            out = self.mixer(n)
        elif self.kind == "mamba":
            out, *extra = self.mixer(n, return_memory=True)
        else:
            out, *extra = self.mixer(n, return_kv=True)
        h = x + out
        h = h + self.mlp(self.post_attention_layernorm(h))
        return (h, *extra) if extra else h


class Phi4FlashForCausalLM(nn.Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = nn.Embedding(config.vocab_size,
                                         config.hidden_size)
        self.layers = nn.LayerList(
            [Phi4FlashBlock(config, i)
             for i in range(config.num_hidden_layers)])
        self.final_layernorm = nn.LayerNorm(config.hidden_size,
                                            config.layer_norm_eps)
        # every matrix normal(0, initializer_range) but the taps and A_log,
        # the lambda vectors normal(0, lambda_std), norm scales 1, biases 0
        # but the convolution's and dt_proj's (nn.MambaMixer)
        init = I.Normal(0.0, config.initializer_range)
        lam = I.Normal(0.0, config.lambda_std)
        for name, p in self.named_parameters():
            if ".lambda_" in name:
                p.set_value(lam(tuple(p.shape), "float32"))
            elif len(p.shape) >= 2 and not name.endswith(
                    ("conv_weight", "A_log")):
                p.set_value(init(tuple(p.shape), "float32"))

    def handed_to(self, block, h, handed):
        """What ``block`` reads of another block: what the giver of its
        kind left in ``handed`` (nothing for a block with a mixer of its
        own), whatever its own input ``h`` is."""
        return handed.get(block.kind, ())

    def forward(self, input_ids):
        from .. import jit
        c = self.config
        h = self.embed_tokens(input_ids)
        if amp.is_enabled():
            h = h.astype(amp.compute_dtype())
        handed = {}             # what a giver left, by its readers' kind
        for block in self.layers:
            reads = self.handed_to(block, h, handed)
            out = jit.recompute(block, h, *reads) if c.recompute \
                else block(h, *reads)
            if block.gives:
                h, *extra = out
                handed["memory_unit" if block.kind == "mamba"
                       else "cross_attention"] = tuple(extra)
            else:
                h = out
            if reads and c.recompute:
                # tensors that cross a checkpoint's boundary, and their
                # bytes: the reader's block holds them beside its input
                monitor.counter("recompute.handed_on").inc(len(reads))
                monitor.counter("recompute.handed_on_bytes").inc(sum(
                    t.size * t.dtype.itemsize for t in reads))
        # the head is the embedding: one leaf, two gradients
        return ops.matmul(self.final_layernorm(h), self.embed_tokens.weight,
                          transpose_y=True)

    def loss(self, logits, input_ids):
        """Mean next-token cross entropy over the predicted positions of
        every sequence (``models/lfm2.py``: the labels shifted, a
        sequence's last position ignored, the logits whole)."""
        b = input_ids.shape[0]
        labels = manip.concat(
            [input_ids[:, 1:], ops.full([b, 1], -100, dtype=input_ids.dtype)],
            axis=1)
        return ops.loss.cross_entropy(logits, labels, ignore_index=-100)
