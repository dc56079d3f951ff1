"""Plain reference for block-diffusion training of ``sdar_moe``
(SDAR-30B-A3B-Chat, a Qwen3-MoE-shaped model trained by diffusion over
blocks): float32 ``jax.numpy``, no kernels, nothing of paddle_tpu.

Model: the keys of the published ``config.json`` (``model_type:
sdar_moe``); the layer of the ``sdar_moe`` / ``qwen3_moe`` model codes;
the training pass of Arriola et al., "Block Diffusion", arXiv:2503.09573
section 3. ``eps = rms_norm_eps`` in every RMS norm, no bias, the head
untied from the embedding. Pre-norm blocks on one residual stream: ``h <-
h + Attn(RMSNorm(h))``, then ``h <- h + MoE(RMSNorm(h))``.

Input: clean ids ``x`` [L], noisy ids (``x`` with ``mask_token_id`` at the
masked positions), weights ``w`` [L] (``1 / t`` at a position masked at
noise level ``t``, 0 elsewhere). The stack sees ``2 L`` rows: rows ``[0,
L)`` embed the noisy ids (copy 0), rows ``[L, 2 L)`` the clean ids (copy
1). Row ``r`` has position ``p = r mod L``, block ``b = p // block_length``
and copy ``c = r // L``.

``Attn``  ``q = u W_q`` (``num_attention_heads`` x ``head_dim``), ``k = u
       W_k``, ``v = u W_v`` (``num_key_value_heads`` x ``head_dim``);
       ``q^ = R_p(RMSNorm(q))``, ``k^ = R_p(RMSNorm(k))``: the head norm
       is over ``head_dim`` with one scale for q and one for k, shared by
       the heads; ``R`` pairs ``(x_j, x_{j + D/2})`` and turns pair ``j``
       by ``p rope_theta ** (-2 j / D)``. KV head ``j`` serves the query
       heads ``[j g, (j + 1) g)``. Row r may attend to row s where
       ``[c(s) = 1 and b(s) < b(r)] or [c(s) = c(r) and b(s) = b(r)]``:
       soft-max of ``q^_r . k^_s / sqrt(D)`` over the allowed s; ``Attn(u)
       = concat_h(o_h) W_o``.
``MoE``   ``p = softmax(u W_r)`` over all published experts, float32;
       chosen = top-k of p; ``g_e = p_e / sum_chosen p`` (``norm_topk_prob``
       true); ``MoE(u) = sum_{e chosen and held} g_e W_down,e (silu(W_gate,e
       u) * W_up,e u)``. No shared expert. **The share**:
       ``cfg["num_experts"]`` counts the experts held here,
       ``first_expert_held .. + num_experts`` of
       ``num_experts_published``; the router keeps the published width and
       what the absent experts would add is left out.

``logits = Head(RMSNorm(h[:L]))`` (the noisy copy's rows; the clean copy's
give keys and values and no logits); ``loss = (1 / L) sum_i w_i CE(
logits_i, x_i)``, no shift, a mean over sequences too.

Departures from the source, each for a reason:

* The source publishes generation; its training pass is taken from the
  Block Diffusion paper (one pass over ``[x_t ; x_0]`` under the
  three-part mask) with ``block_length`` 4, the family's released
  default, and the masked-diffusion loss ``w = 1 / t`` (configuration
  file, ``assumed``).
* Attention is walked one head at a time and a block of query rows at a
  time, each recomputed in the backward pass, so that 16,384 x 16,384
  scores of 32 heads are never held: the mask is made for a block of rows
  from their positions. Every held expert is applied to every row of a
  block (one ``lax.scan`` body) and weighted by the router's weight or
  zero; head and loss walk the positions in blocks. Each block of the
  stack is recomputed (``jax.checkpoint``) and sequences are walked one at
  a time, so that three steps at the timed size fit beside 16 bytes a
  parameter.
* No auxiliary balance loss (``router_aux_loss_coef`` is not used), no
  document boundaries: the step is a function of the seed.

Names are the program's (the source's state dict without ``model.``; the
router ``mlp.router.weight``, the experts stacked ``[held, in, out]``), so
that the same seeded arrays load there: a linear weight is ``[in, out]``.

Optimizer: AdamW with decay on every parameter (``bert_pretrain.py``).
"""
import math

import numpy as np

import jax
import jax.numpy as jnp

from .bert_pretrain import adamw_update
from .common import diff_norms, leaf_norms, matrix_leaves, seed_key
from .nemotron_h import _by_token_blocks, _ein, _rms_norm


def param_shapes(cfg):
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    held, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    shapes = {"embed_tokens.weight": (v, d), "norm.weight": (d,),
              "lm_head.weight": (d, v)}
    for i in range(cfg["num_hidden_layers"]):
        a, m = f"layers.{i}.self_attn.", f"layers.{i}.mlp."
        shapes.update({
            f"layers.{i}.input_layernorm.weight": (d,),
            f"layers.{i}.post_attention_layernorm.weight": (d,),
            a + "q_proj.weight": (d, heads * hd),
            a + "k_proj.weight": (d, kv * hd),
            a + "v_proj.weight": (d, kv * hd),
            a + "q_norm.weight": (hd,), a + "k_norm.weight": (hd,),
            a + "o_proj.weight": (heads * hd, d),
            m + "router.weight": (d, cfg["num_experts_published"]),
            m + "experts_gate": (held, d, f), m + "experts_up": (held, d, f),
            m + "experts_down": (held, f, d)})
    return shapes


def compared_leaves(cfg):
    return matrix_leaves(param_shapes(cfg))


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call: unit
    norm scales, matrices normal(0, ``initializer_range``), but (each key
    optional, each named under the configuration file's ``assumed``)

    * the embedding normal(0, ``embedding_initializer_range``);
    * with ``rescale_prenorm_residual`` the two projections of a layer
      that write to the residual stream (``o_proj``, ``experts_down``)
      divided by ``sqrt(2 * num_hidden_layers)``;
    * with ``experts_numbered_by_mask_rank`` the experts of each layer
      numbered in the order in which the mask token's embedding ranks them
      through that layer's router, the lowest score first (the router's
      columns are put in that order: the experts' own matrices are
      independent draws, so this is a numbering and nothing else)."""
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
        p = {name: jnp.ones(shape, jnp.float32) if len(shape) == 1
             else scale(name) * jax.random.normal(jax.random.fold_in(key, i),
                                                  shape, jnp.float32)
             for i, (name, shape) in enumerate(sorted(shapes.items()))}
        if cfg.get("experts_numbered_by_mask_rank", False):
            mask_row = _rms_norm(
                p["embed_tokens.weight"][cfg["mask_token_id"]],
                jnp.ones(cfg["hidden_size"]), cfg["rms_norm_eps"])
            for i in range(cfg["num_hidden_layers"]):
                name = f"layers.{i}.mlp.router.weight"
                scores = jnp.einsum("d,de->e", mask_row, p[name],
                                    precision="highest")
                p[name] = p[name][:, jnp.argsort(scores)]
        return p

    return jax.jit(make)(seed_key(seed))


def allowed(rows, keys, length, block):
    """bool [len(rows), len(keys)]: whether row ``r`` may attend to row
    ``s`` of the ``2 * length`` rows (module docstring)."""
    def parts(at):
        return at // length, (at % length) // block
    c_r, b_r = parts(rows[:, None])
    c_s, b_s = parts(keys[None, :])
    return ((c_s == 1) & (b_s < b_r)) | ((c_s == c_r) & (b_s == b_r))


def rotary(x, positions, theta):
    """``R_p(x)``: ``x`` [S, ..., D] with row ``i`` at ``positions[i]``;
    pairs ``(x_j, x_{j + D/2})``. Angles and rotation in float32, the
    frequencies made in float64 on the host."""
    d = x.shape[-1]
    freq = np.asarray(float(theta) ** (-2.0 * np.arange(d // 2) / d),
                      np.float32)
    angle = positions.astype(jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1)) * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def qkv(cfg, w, u, ein):
    """(q, k, v), each [2 L, num_attention_heads, head_dim], of the two
    copies' rows ``u`` [2 L, hidden]; the K/V heads repeated to the query
    heads they serve."""
    rows, hd = u.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    at = jnp.arange(rows) % (rows // 2)
    q = ein("sd,de->se", u, w["q_proj.weight"]).reshape(rows, heads, hd)
    k = ein("sd,de->se", u, w["k_proj.weight"]).reshape(rows, kv, hd)
    v = ein("sd,de->se", u, w["v_proj.weight"]).reshape(rows, kv, hd)
    q = rotary(_rms_norm(q, w["q_norm.weight"], eps), at, theta)
    k = rotary(_rms_norm(k, w["k_norm.weight"], eps), at, theta)
    return q, jnp.repeat(k, heads // kv, 1), jnp.repeat(v, heads // kv, 1)


def _attention(cfg, w, u, ein):
    rows = u.shape[0]
    q, k, v = qkv(cfg, w, u, ein)
    at = jnp.arange(rows)
    scale = 1.0 / math.sqrt(cfg["head_dim"])

    @jax.checkpoint     # one head at a time, a block of its query rows
    def one_head(args):
        q_h, k_h, v_h = args

        def block(blk):
            q_b, at_b = blk
            scores = ein("qd,kd->qk", q_b, k_h) * scale
            may = allowed(at_b, at, rows // 2, cfg["block_length"])
            probs = jax.nn.softmax(jnp.where(may, scores, -jnp.inf), -1)
            return ein("qk,kd->qd", probs, v_h)

        return _by_token_blocks(block, (q_h, at))

    ctx = jax.lax.map(one_head, tuple(jnp.moveaxis(t, 1, 0)
                                      for t in (q, k, v)))
    return ein("se,ed->sd", jnp.moveaxis(ctx, 0, 1).reshape(rows, -1),
               w["o_proj.weight"])


def route(cfg, u, router_weight):
    """(chosen experts [T, k], their weights [T, k]) of the published
    router: a soft-max over all published experts, top-k, renormalised
    over the chosen; float32."""
    p = jax.nn.softmax(jnp.einsum(
        "td,de->te", u.astype(jnp.float32), router_weight.astype(jnp.float32),
        precision="highest"), -1)
    picked, chosen = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    return chosen, picked / jnp.sum(picked, -1, keepdims=True)


def _gated_mlp(u, gate, up, down, ein):
    return ein("tf,fd->td", jax.nn.silu(ein("td,df->tf", u, gate))
               * ein("td,df->tf", u, up), down)


def _moe(cfg, w, u, ein):
    """This share's part of the layer: the held experts' weighted
    outputs."""
    chosen, weights = route(cfg, u, w["router.weight"])
    first = cfg.get("first_expert_held", 0)

    def add_expert(out, held):      # a scan, so the program holds one body
        j, gate_w, up_w, down_w = held
        gate = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        return out + gate[:, None] * _gated_mlp(u, gate_w, up_w, down_w,
                                                ein), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.arange(cfg["num_experts"]), w["experts_gate"],
         w["experts_up"], w["experts_down"]))
    return out


def _under(p, head):
    return {k[len(head):]: v for k, v in p.items() if k.startswith(head)}


def _hidden(cfg, p, noisy, clean, ein):
    """The residual stream after the last block, [2 L, hidden], of one
    sequence's two copies."""
    eps = cfg["rms_norm_eps"]
    h = p["embed_tokens.weight"][jnp.concatenate([noisy, clean])]
    for i in range(cfg["num_hidden_layers"]):
        @jax.checkpoint
        def block(h, w):
            h = h + _attention(cfg, _under(w, "self_attn."),
                               _rms_norm(h, w["input_layernorm.weight"],
                                         eps), ein)
            u = _rms_norm(h, w["post_attention_layernorm.weight"], eps)
            return h + _by_token_blocks(
                lambda blk: _moe(cfg, _under(w, "mlp."), blk, ein), u)
        h = block(h, _under(p, f"layers.{i}."))
    return h


def _logits(cfg, p, h, ein):
    return ein("sd,dv->sv", _rms_norm(h, p["norm.weight"],
                                      cfg["rms_norm_eps"]),
               p["lm_head.weight"])


def forward(cfg, p, noisy_ids, clean_ids, precision="float32"):
    """Logits [B, L, V] of the noisy copy's rows, one sequence at a
    time."""
    ein = _ein(precision)
    seq = noisy_ids.shape[1]
    return jax.lax.map(
        lambda ids: _logits(cfg, p, _hidden(cfg, p, *ids, ein)[:seq], ein),
        (noisy_ids, clean_ids))


def loss_fn(cfg, p, batch, precision="float32"):
    """``(1 / (B L)) sum_i w_i CE(logits_i, clean_i)``; head and loss walk
    the positions in blocks, so that no whole [L, V] logits are held."""
    clean_ids, noisy_ids, weights = batch
    ein = _ein(precision)
    rows, seq = clean_ids.shape

    def sequence(args):
        clean, noisy, w = args
        h = _hidden(cfg, p, noisy, clean, ein)[:seq]

        def block(blk):
            h_b, labels_b, w_b = blk
            logits = _logits(cfg, p, h_b, ein)
            picked = jnp.take_along_axis(logits, labels_b[:, None], -1)[:, 0]
            return w_b * (jax.nn.logsumexp(logits, -1) - picked)

        return jnp.sum(_by_token_blocks(block, (h, clean, w)))

    return jnp.sum(jax.lax.map(
        sequence, (clean_ids, noisy_ids, weights.astype(jnp.float32)))) \
        / (rows * seq)


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
