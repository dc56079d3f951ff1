"""Plain reference for causal pre-training of ``lfm2_moe`` (LFM2-8B-A1B:
gated short-convolution layers beside grouped-query attention layers in one
stack, a dense layer and then sigmoid-routed gated experts, a tied head):
float32 ``jax.numpy``, no kernels, nothing of paddle_tpu.

Model: the keys of the published ``config.json`` (``model_type:
lfm2_moe``); the layer of the ``lfm2`` / ``lfm2_moe`` model codes.
``RMS(x) = x / sqrt(mean(x^2) + norm_eps) * g`` everywhere, no bias
anywhere. For layer ``l`` (the source's layer ``first_layer + l``) with
input ``x`` [T, hidden] of one sequence, on one pre-norm residual stream

    h = x + Op_l(RMS_op(x))          y = h + FF_l(RMS_ffn(h))

``Op``, ``layer_types[first_layer + l] == "conv"``: ``[b | c | u] = n
       W_in`` (hidden -> 3 hidden, the thirds in this order: the source's
       B, C, x); ``v = b * u``; ``z_t = sum_j k_j * v_{t - (K - 1) + j}``
       per channel, ``K = conv_L_cache``, ``v`` zero in front of the
       sequence's first position (the published ``Conv1d(groups=hidden,
       kernel K, padding K - 1)`` cut to ``T`` rows, no bias, no
       activation); ``Op = (c * z) W_out``. ``k`` is ``[hidden, K]``: tap
       ``K - 1`` multiplies the current position.
``Op``, ``"full_attention"``: ``q = n W_q`` (``num_attention_heads`` x
       ``hidden / num_attention_heads``), ``k = n W_k``, ``v = n W_v``
       (``num_key_value_heads`` heads); ``q`` and ``k`` each through an RMS
       norm over the head's width (one scale a side); rotary, pairs
       ``(x_j, x_{j + D/2})`` turned by ``p rope_theta ** (-2 j / D)`` at
       position ``p = 0..T-1``; causal soft-max of ``q k^T / sqrt(D)``; KV
       head ``g`` serves the query heads ``[g n, (g + 1) n)``; ``W_o``.
``FF``, ``l < num_dense_layers``: ``W_2 (silu(W_1 m) * W_3 m)`` of
       ``intermediate_size`` (names ``gate_proj``, ``up_proj``,
       ``down_proj``).
``FF``, an expert layer: ``s = sigmoid(m W_r)`` in float32 over all
       published experts; chosen = top-k of ``s + bias`` (``bias`` a
       buffer, zero: ``use_expert_bias``); ``w_i = routed_scaling_factor
       s_i / (sum_chosen s + 1e-6)`` (the published epsilon; the program's
       ``F.moe_route`` divides by ``sum + 1e-20``, at most 5e-7 of a
       weight apart: configuration file, ``assumed``); ``sum_{i chosen and
       held} w_i W_2^i (silu(W_1^i m) * W_3^i m)`` of
       ``moe_intermediate_size``. **The share**: ``cfg["num_experts"]``
       counts the experts held here, ``first_expert_held .. +
       num_experts`` of ``num_experts_published``; the router keeps the
       published width and what the absent experts would add is left out.

After the last layer ``RMS_final`` (the source's ``embedding_norm``) and
``logits = h E^T`` with ``E`` the embedding: ONE leaf, whose gradient is
the look-up's plus the head's. The loss is the mean next-token cross
entropy over the predicted positions (position t's logits against token
t + 1 of the same sequence), a mean over sequences too.

Departures from the source, each for a reason:

* Attention is walked one head at a time and a block of query rows at a
  time, each recomputed in the backward pass; every held expert is
  applied to every row of a block (one ``lax.scan`` body) and weighted by
  the router's weight or zero; head and loss walk the positions in
  blocks. Each block of the stack is recomputed (``jax.checkpoint``) and
  sequences are walked one at a time (so a row of one sequence cannot
  read another's: the convolution's zeros in front of a sequence are this
  file's ``jnp.pad`` of ONE sequence), so that three steps at the timed
  size fit beside 16 bytes a parameter.
* No auxiliary balance loss, no update of the selection bias, no document
  boundaries: the step is a function of the seed.

Names are the program's (``paddle_tpu/models/lfm2.py``), so that the same
seeded arrays load there: a linear weight is ``[in, out]``.

Optimizer: AdamW with decay on every parameter (``bert_pretrain.py``).
"""
import math

import jax
import jax.numpy as jnp

from .bert_pretrain import adamw_update
from .common import diff_norms, leaf_norms, matrix_leaves, seed_key
from .nemotron_h import _by_token_blocks, _ein, _rms_norm
from .sdar_moe import _gated_mlp, _under, rotary

ROUTE_EPSILON = 1e-6        # the published router's, under the chosen's sum


def layer_kinds(cfg):
    """[(operator kind, whether the feed-forward is dense)] a held layer."""
    first, layers = cfg.get("first_layer", 0), cfg["num_hidden_layers"]
    kinds = cfg["layer_types"][first:first + layers]
    if len(kinds) != layers or set(kinds) - {"conv", "full_attention"}:
        raise ValueError(f"layer_types {cfg['layer_types']!r} does not "
                         f"describe {layers} layers from {first} on")
    return [(kind, i < cfg["num_dense_layers"])
            for i, kind in enumerate(kinds)]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def param_shapes(cfg):
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed_tokens.weight": (v, d), "embedding_norm.weight": (d,)}
    for i, (kind, dense) in enumerate(layer_kinds(cfg)):
        at = f"layers.{i}."
        shapes.update({at + "operator_norm.weight": (d,),
                       at + "ffn_norm.weight": (d,)})
        if kind == "conv":
            shapes.update({
                at + "conv.in_proj.weight": (d, 3 * d),
                at + "conv.conv_weight": (d, cfg["conv_L_cache"]),
                at + "conv.out_proj.weight": (d, d)})
        else:
            a = at + "self_attn."
            shapes.update({
                a + "q_proj.weight": (d, heads * hd),
                a + "k_proj.weight": (d, kv * hd),
                a + "v_proj.weight": (d, kv * hd),
                a + "q_norm.weight": (hd,), a + "k_norm.weight": (hd,),
                a + "o_proj.weight": (heads * hd, d)})
        m = at + "feed_forward."
        if dense:
            f_d = cfg["intermediate_size"]
            shapes.update({m + "gate_proj.weight": (d, f_d),
                           m + "up_proj.weight": (d, f_d),
                           m + "down_proj.weight": (f_d, d)})
        else:
            shapes.update({
                m + "router.weight": (d, cfg["num_experts_published"]),
                m + "experts_gate": (held, d, f),
                m + "experts_up": (held, d, f),
                m + "experts_down": (held, f, d)})
    return shapes


def compared_leaves(cfg):
    return matrix_leaves(param_shapes(cfg))


_WRITERS = ("out_proj.weight", "o_proj.weight", "down_proj.weight",
            "experts_down")


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call
    (configuration file, ``assumed.weights``): unit norm scales; the taps
    uniform in ``+- 1 / sqrt(conv_L_cache)``; matrices normal(0,
    ``initializer_range``), but the embedding normal(0,
    ``embedding_initializer_range``) and, with
    ``rescale_prenorm_residual``, the projections that write to the
    residual stream (``out_proj`` of the convolution, ``o_proj``, the dense
    layer's ``down_proj``, ``experts_down``) divided by ``sqrt(2 *
    num_hidden_layers)``."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]
    embed_std = cfg.get("embedding_initializer_range", std)
    writer = 1.0
    if cfg.get("rescale_prenorm_residual", False):
        writer = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])
    tap = 1.0 / math.sqrt(cfg["conv_L_cache"])

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if len(shape) == 1:
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("conv_weight"):
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -tap, tap)
            else:
                scale = embed_std if name == "embed_tokens.weight" \
                    else std * writer if name.endswith(_WRITERS) else std
                out[name] = scale * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def gated_conv(b, c, u, taps_w, ein):
    """``c * conv(b * u)`` of one sequence: ``b``, ``c``, ``u`` [T, C],
    ``taps_w`` [C, K]. The shifted products are a bilinear map of ``v`` and
    the taps, so they take the precision asked for."""
    s, taps = b.shape[0], taps_w.shape[1]
    v = jnp.pad(b * u, [(taps - 1, 0), (0, 0)])
    shifted = jnp.stack([v[j:j + s] for j in range(taps)], -1)   # [T, C, K]
    return c * ein("tck,ck->tc", shifted, taps_w)


def _short_conv(cfg, w, n, ein):
    bcx = ein("sd,de->se", n, w["in_proj.weight"])
    b, c, u = jnp.split(bcx, 3, axis=-1)
    return ein("se,ed->sd", gated_conv(b, c, u, w["conv_weight"], ein),
               w["out_proj.weight"])


def _attention(cfg, w, n, ein):
    s, hd = n.shape[0], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    at = jnp.arange(s)
    q = ein("sd,de->se", n, w["q_proj.weight"]).reshape(s, heads, hd)
    k = ein("sd,de->se", n, w["k_proj.weight"]).reshape(s, kv, hd)
    v = ein("sd,de->se", n, w["v_proj.weight"]).reshape(s, kv, hd)
    q = rotary(_rms_norm(q, w["q_norm.weight"], eps), at, theta)
    k = rotary(_rms_norm(k, w["k_norm.weight"], eps), at, theta)

    @jax.checkpoint     # one query head at a time, a block of its rows
    def one_head(args):
        q_h, k_g, v_g = args

        def rows(blk):
            q_b, at_b = blk
            scores = ein("qd,kd->qk", q_b, k_g) / math.sqrt(hd)
            probs = jax.nn.softmax(
                jnp.where(at_b[:, None] >= at[None, :], scores, -jnp.inf), -1)
            return ein("qk,kd->qd", probs, v_g)

        return _by_token_blocks(rows, (q_h, at))

    serves = jnp.arange(heads) // (heads // kv)      # query head -> KV head
    ctx = jax.lax.map(one_head, (jnp.moveaxis(q, 1, 0),
                                 jnp.moveaxis(k, 1, 0)[serves],
                                 jnp.moveaxis(v, 1, 0)[serves]))
    return ein("se,ed->sd", jnp.moveaxis(ctx, 0, 1).reshape(s, heads * hd),
               w["o_proj.weight"])


def route(cfg, m, router_weight, bias=None):
    """(chosen experts [T, k], their weights [T, k]) of the published
    router, over all published experts, in float32."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", m.astype(jnp.float32), router_weight.astype(jnp.float32),
        precision="highest"))
    _, chosen = jax.lax.top_k(s if bias is None else s + bias,
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, -1)
    weights = cfg["routed_scaling_factor"] * picked \
        / (jnp.sum(picked, -1, keepdims=True) + ROUTE_EPSILON)
    return chosen, weights


def _moe(cfg, w, m, ein):
    """This share's part of the layer: the held experts' weighted
    outputs."""
    chosen, weights = route(cfg, m, w["router.weight"])
    first = cfg.get("first_expert_held", 0)

    def add_expert(out, held):      # a scan, so the program holds one body
        j, gate_w, up_w, down_w = held
        gate = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        return out + gate[:, None] * _gated_mlp(m, gate_w, up_w, down_w,
                                                ein), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(m),
        (jnp.arange(cfg["num_experts"]), w["experts_gate"],
         w["experts_up"], w["experts_down"]))
    return out


def _feed_forward(cfg, w, m, dense, ein):
    if dense:
        return _by_token_blocks(
            lambda blk: _gated_mlp(blk, w["gate_proj.weight"],
                                   w["up_proj.weight"],
                                   w["down_proj.weight"], ein), m)
    return _by_token_blocks(lambda blk: _moe(cfg, w, blk, ein), m)


def _hidden(cfg, p, ids, ein):
    """The residual stream after the last block, [T, hidden], of one
    sequence."""
    eps = cfg["norm_eps"]
    x = p["embed_tokens.weight"][ids]
    for i, (kind, dense) in enumerate(layer_kinds(cfg)):
        @jax.checkpoint
        def block(x, w, kind=kind, dense=dense):
            n = _rms_norm(x, w["operator_norm.weight"], eps)
            h = x + (_short_conv(cfg, _under(w, "conv."), n, ein)
                     if kind == "conv" else
                     _attention(cfg, _under(w, "self_attn."), n, ein))
            return h + _feed_forward(
                cfg, _under(w, "feed_forward."),
                _rms_norm(h, w["ffn_norm.weight"], eps), dense, ein)
        x = block(x, _under(p, f"layers.{i}."))
    return x


def _logits(cfg, p, h, ein):
    return ein("sd,vd->sv", _rms_norm(h, p["embedding_norm.weight"],
                                      cfg["norm_eps"]),
               p["embed_tokens.weight"])


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
