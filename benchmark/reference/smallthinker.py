"""Plain reference for causal pre-training of ``smallthinker``
(SmallThinker-21BA3B-Instruct: window layers with rotary positions and
global layers without positions in one stack, a router that reads the
layer's input ahead of attention, ReLU-gated experts): float32
``jax.numpy``, no kernels, nothing of paddle_tpu.

Model: the keys of the published ``config.json`` (``model_name:
smallthinker_21b_instruct``); the layer of the ``smallthinker`` model
code. ``eps = rms_norm_eps`` in every RMS norm, no bias, the head untied
from the embedding, no shared expert, no dense layer. For layer ``l`` with
input ``x`` [T, hidden] on one pre-norm residual stream:

``r``     ``x W_r``: the router's logits over all published experts,
       float32, from the layer's INPUT, ahead of the norm and of
       attention.
``Attn``  ``a = RMSNorm_in(x)``; ``q = a W_q`` (``num_attention_heads`` x
       ``head_dim``), ``k = a W_k``, ``v = a W_v``
       (``num_key_value_heads`` x ``head_dim``); where ``rope_layout[l]``
       is 1, ``q`` and ``k`` are rotated: pairs ``(x_j, x_{j + D/2})``
       turned by ``p rope_theta ** (-2 j / D)`` at position ``p``; where it
       is 0 the layer has NO position embedding. Row ``i`` may attend to
       key ``j`` where ``j <= i`` and, if ``sliding_window_layout[l]`` is
       1, ``i - j < sliding_window_size`` (a row sees itself and the
       ``sliding_window_size - 1`` positions before it). KV head ``g``
       serves the query heads ``[g n, (g + 1) n)``. Soft-max of ``q_i . k_j
       / sqrt(D)`` over the allowed ``j``; ``h = x + concat_h(o_h) W_o``.
``MoE``   ``m = RMSNorm_post(h)``; ``p = softmax(r)`` over all published
       experts; chosen = top-k of ``p``; ``g_e = p_e / sum_chosen p``
       (``norm_topk_prob``); ``y = h + sum_{e chosen and held} g_e W_down,e
       (relu(W_gate,e m) * W_up,e m)``. **The share**:
       ``cfg["moe_num_primary_experts"]`` counts the experts held here,
       ``first_expert_held .. + moe_num_primary_experts`` of
       ``moe_num_primary_experts_published``; the router keeps the
       published width and what the absent experts would add is left out.

``logits = Head(RMSNorm(y_last))``; the loss is the mean next-token cross
entropy over the predicted positions (position t's logits against token
t + 1), a mean over sequences too.

Departures from the source, each for a reason:

* The router's input is the un-normalised layer input (the form the
  public ports of this model use; the catalog's "router placed before
  attention"); ``config.json`` does not say which tensor it reads
  (configuration file, ``assumed``).
* Attention is walked one head at a time and a block of query rows at a
  time, each recomputed in the backward pass, so that 16,384 x 16,384
  scores of 28 heads are never held: the mask is made for a block of rows
  from their positions. Every held expert is applied to every row of a
  block (one ``lax.scan`` body) and weighted by the router's weight or
  zero; head and loss walk the positions in blocks. Each block of the
  stack is recomputed (``jax.checkpoint``) and sequences are walked one at
  a time, so that three steps at the timed size fit beside 16 bytes a
  parameter.
* No auxiliary balance loss, no document boundaries: the step is a
  function of the seed.

Names are the program's (the source's state dict without ``model.``; the
router ``block_sparse_moe.router.weight``, the experts stacked ``[held,
in, out]``), so that the same seeded arrays load there: a linear weight is
``[in, out]``.

Optimizer: AdamW with decay on every parameter (``bert_pretrain.py``).
"""
import math

import jax
import jax.numpy as jnp

from .bert_pretrain import adamw_update
from .common import diff_norms, leaf_norms, matrix_leaves, seed_key
from .nemotron_h import _by_token_blocks, _ein, _rms_norm
from .sdar_moe import _under, rotary


def param_shapes(cfg):
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["moe_num_primary_experts"], cfg["moe_ffn_hidden_size"]
    shapes = {"embed_tokens.weight": (v, d), "norm.weight": (d,),
              "lm_head.weight": (d, v)}
    for i in range(cfg["num_hidden_layers"]):
        a, m = f"layers.{i}.self_attn.", f"layers.{i}.block_sparse_moe."
        shapes.update({
            f"layers.{i}.input_layernorm.weight": (d,),
            f"layers.{i}.post_attention_layernorm.weight": (d,),
            a + "q_proj.weight": (d, heads * hd),
            a + "k_proj.weight": (d, kv * hd),
            a + "v_proj.weight": (d, kv * hd),
            a + "o_proj.weight": (heads * hd, d),
            m + "router.weight": (d, cfg["moe_num_primary_experts_published"]),
            m + "experts_gate": (held, d, f), m + "experts_up": (held, d, f),
            m + "experts_down": (held, f, d)})
    return shapes


def compared_leaves(cfg):
    return matrix_leaves(param_shapes(cfg))


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call: unit
    norm scales, matrices normal(0, ``initializer_range``), but (each key
    optional, each named under the configuration file's ``assumed``) the
    embedding normal(0, ``embedding_initializer_range``) and, with
    ``rescale_prenorm_residual``, the two projections of a layer that
    write to the residual stream (``o_proj``, ``experts_down``) divided by
    ``sqrt(2 * num_hidden_layers)``."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]
    embed_std = cfg.get("embedding_initializer_range", std)
    writer = 1.0
    if cfg.get("rescale_prenorm_residual", False):
        writer = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])

    def scale(name):
        if name == "embed_tokens.weight":
            return embed_std
        if name.endswith(("o_proj.weight", "experts_down")):
            return std * writer
        return std

    def make(key):
        return {name: jnp.ones(shape, jnp.float32) if len(shape) == 1
                else scale(name) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
                for i, (name, shape) in enumerate(sorted(shapes.items()))}

    return jax.jit(make)(seed_key(seed))


def allowed(rows, keys, window=None):
    """bool [len(rows), len(keys)]: whether the row at position ``i`` may
    attend to the key at position ``j``: ``j <= i`` and, under a
    ``window``, ``i - j < window``."""
    gap = rows[:, None] - keys[None, :]
    return (gap >= 0) if window is None else (gap >= 0) & (gap < window)


def _attention(cfg, w, u, layer, ein):
    s, hd = u.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    at = jnp.arange(s)
    window = cfg["sliding_window_size"] \
        if cfg["sliding_window_layout"][layer] else None
    q = ein("sd,de->se", u, w["q_proj.weight"]).reshape(s, heads, hd)
    k = ein("sd,de->se", u, w["k_proj.weight"]).reshape(s, kv, hd)
    v = ein("sd,de->se", u, w["v_proj.weight"]).reshape(s, kv, hd)
    if cfg["rope_layout"][layer]:
        q, k = (rotary(t, at, cfg["rope_theta"]) for t in (q, k))

    @jax.checkpoint     # one query head at a time, a block of its rows
    def one_head(args):
        q_h, k_g, v_g = args

        def rows(blk):
            q_b, at_b = blk
            scores = ein("qd,kd->qk", q_b, k_g) / math.sqrt(hd)
            probs = jax.nn.softmax(jnp.where(allowed(at_b, at, window),
                                             scores, -jnp.inf), -1)
            return ein("qk,kd->qd", probs, v_g)

        return _by_token_blocks(rows, (q_h, at))

    serves = jnp.arange(heads) // (heads // kv)      # query head -> KV head
    ctx = jax.lax.map(one_head, (jnp.moveaxis(q, 1, 0),
                                 jnp.moveaxis(k, 1, 0)[serves],
                                 jnp.moveaxis(v, 1, 0)[serves]))
    return ein("se,ed->sd", jnp.moveaxis(ctx, 0, 1).reshape(s, heads * hd),
               w["o_proj.weight"])


def route(cfg, x, router_weight):
    """(chosen experts [T, k], their weights [T, k]) of the published
    router on its input ``x``: a soft-max over all published experts,
    top-k, renormalised over the chosen; float32."""
    p = jax.nn.softmax(jnp.einsum(
        "td,de->te", x.astype(jnp.float32), router_weight.astype(jnp.float32),
        precision="highest"), -1)
    picked, chosen = jax.lax.top_k(p, cfg["moe_num_active_primary_experts"])
    return chosen, picked / jnp.sum(picked, -1, keepdims=True)


def _relu_gated_mlp(m, gate, up, down, ein):
    return ein("tf,fd->td", jax.nn.relu(ein("td,df->tf", m, gate))
               * ein("td,df->tf", m, up), down)


def _moe(cfg, w, x, m, ein):
    """This share's part of the layer: the held experts' outputs on ``m``,
    weighted as the router says of ``x``."""
    chosen, weights = route(cfg, x, w["router.weight"])
    first = cfg.get("first_expert_held", 0)

    def add_expert(out, held):      # a scan, so the program holds one body
        j, gate_w, up_w, down_w = held
        gate = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        return out + gate[:, None] * _relu_gated_mlp(m, gate_w, up_w, down_w,
                                                     ein), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (jnp.arange(cfg["moe_num_primary_experts"]), w["experts_gate"],
         w["experts_up"], w["experts_down"]))
    return out


def _hidden(cfg, p, ids, ein):
    """The residual stream after the last block, [T, hidden], of one
    sequence."""
    eps = cfg["rms_norm_eps"]
    x = p["embed_tokens.weight"][ids]
    for i in range(cfg["num_hidden_layers"]):
        @jax.checkpoint
        def block(x, w, i=i):
            h = x + _attention(cfg, _under(w, "self_attn."),
                               _rms_norm(x, w["input_layernorm.weight"],
                                         eps), i, ein)
            m = _rms_norm(h, w["post_attention_layernorm.weight"], eps)
            return h + _by_token_blocks(
                lambda blk: _moe(cfg, _under(w, "block_sparse_moe."), *blk,
                                 ein), (x, m))
        x = block(x, _under(p, f"layers.{i}."))
    return x


def _logits(cfg, p, h, ein):
    return ein("sd,dv->sv", _rms_norm(h, p["norm.weight"],
                                      cfg["rms_norm_eps"]),
               p["lm_head.weight"])


def forward(cfg, p, ids, precision="float32"):
    """Logits [B, T, V] of ids [B, T], one sequence at a time."""
    ein = _ein(precision)
    return jax.lax.map(
        lambda row: _logits(cfg, p, _hidden(cfg, p, row, ein), ein), ids)


def loss_fn(cfg, p, batch, precision="float32"):
    """Mean next-token cross entropy over the predicted positions; head
    and loss walk the positions in blocks, so that no whole [T, V] logits
    are held."""
    (ids,) = batch
    ein = _ein(precision)
    rows, seq = ids.shape
    labels = jnp.concatenate([ids[:, 1:], jnp.zeros((rows, 1), ids.dtype)], 1)
    predicts = jnp.arange(seq) < seq - 1

    def sequence(args):
        row, row_labels = args
        h = _hidden(cfg, p, row, ein)

        def block(blk):
            h_b, labels_b, predicts_b = blk
            logits = _logits(cfg, p, h_b, ein)
            picked = jnp.take_along_axis(logits, labels_b[:, None], -1)[:, 0]
            ce = jax.nn.logsumexp(logits, -1) - picked
            return jnp.where(predicts_b, ce, 0.0)

        return jnp.sum(_by_token_blocks(block, (h, row_labels, predicts)))

    return jnp.sum(jax.lax.map(sequence, (ids, labels))) \
        / (rows * (seq - 1))


def train(cfg, hyper, seed, batches, precision="float32"):
    """Follow ``len(batches)`` optimizer steps from the seed's weights and
    return what ``common.follow`` returns: each step's loss, the norm of
    every leaf of the first gradient, the norm of every leaf's change after
    the last step. As ``nemotron_h.train``: parameters and moments are
    donated to each step and the seed's weights are made a second time for
    the change, so that the chip holds 16 bytes a parameter and never 24."""
    def step(p, m, v, t, batch):
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(cfg, q, batch, precision))(p)
        new = {k: adamw_update(hyper, p[k], g[k], m[k], v[k], t) for k in p}
        return tuple({k: n[i] for k, n in new.items()} for i in range(3)) \
            + (loss, leaf_norms(g))

    with jax.default_matmul_precision("highest"):
        jstep = jax.jit(step, donate_argnums=(0, 1, 2))
        p = init_weights(cfg, seed)
        m, v = (jax.tree.map(jnp.zeros_like, p) for _ in range(2))
        losses, first = [], None
        for t, batch in enumerate(batches, 1):
            p, m, v, loss, norms = jstep(
                p, m, v, jnp.float32(t), tuple(jnp.asarray(a) for a in batch))
            losses.append(float(loss))
            if first is None:
                first = jax.device_get(norms)
        del m, v
        delta = jax.device_get(jax.jit(diff_norms)(p, init_weights(cfg, seed)))
    return {"loss": losses,
            "first_grad_norm": {k: float(x) for k, x in first.items()},
            "delta_norm": {k: float(x) for k, x in delta.items()}}
