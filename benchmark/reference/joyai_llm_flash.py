"""Plain reference for causal pre-training of ``joyai_llm_flash``
(JoyAI-LLM-Flash, a DeepSeek-V3-style model): float32 ``jax.numpy``, no
kernels, nothing of paddle_tpu.

Model: the keys of the published ``config.json`` (``model_type:
joyai_llm_flash``), the equations of the DeepSeek-V2 / V3 reports
(arXiv:2405.04434 section 2.1, arXiv:2412.19437 sections 2.1-2.2) that
those keys come from. ``eps = rms_norm_eps`` in every RMS norm, no bias,
the head untied from the embedding. A residual stream of pre-norm blocks,
``h <- h + Attn(RMSNorm(h))``, then ``h <- h + FFN(RMSNorm(h))``.

Rotary embedding (``rope_interleave`` true, ``rope_scaling`` null): for
position ``p`` and pair ``j < D / 2``, ``theta_j = rope_theta ** (-2 j /
D)``; ``R(x)_j = x_{2j} cos(p theta_j) - x_{2j+1} sin(p theta_j)``,
``R(x)_{j + D/2} = x_{2j+1} cos(p theta_j) + x_{2j} sin(p theta_j)`` (the
source's code de-interleaves and then rotates halves).

``Attn``  multi-head latent attention: ``c_q = RMSNorm(u W_qa)``;
       ``[q_nope | q_rope]_h = c_q W_qb``; ``[c_kv | k_r] = u W_kva``;
       ``[k_nope | v]_h = RMSNorm(c_kv) W_kvb``; ``q_h = [q_nope_h |
       R(q_rope_h)]``, ``k_h = [k_nope_h | R(k_r)]``, one ``k_r`` for all
       heads; ``o_h = softmax(q_h k_h^T / sqrt(d_nope + d_rope) + causal)
       v_h``; ``Attn(u) = concat_h(o_h) W_o``.
``FFN``   layers ``i < first_k_dense_replace``: ``W_down(silu(W_gate u) *
       W_up u)`` at ``intermediate_size``. Every later layer (``i %
       moe_layer_freq == 0``): ``s = sigmoid(u W_r)`` over all published
       experts, float32; chosen = top-k of ``s + b`` (``b`` the selection
       bias, a buffer, zero here; ``n_group = topk_group = 1``: no group
       limit); ``w_i = routed_scaling_factor s_i / (sum_chosen s +
       1e-20)``; ``FFN(u) = sum_{i chosen and held} w_i E_i(u) +
       E_shared(u)``, every ``E`` the gated form at
       ``moe_intermediate_size`` (the shared one ``n_shared_experts``
       times as wide). **The share**: ``cfg["n_routed_experts"]`` counts
       the experts held here, ``first_expert_held .. + n_routed_experts``
       of ``n_routed_experts_published``; the router keeps the published
       width and what the absent experts would add is left out.
MTP    one multi-token-prediction module (``num_nextn_predict_layers``
       1), layer ``L = num_hidden_layers`` of the state dict: with ``h^L``
       the main stack's output BEFORE the final norm, ``x_t = W_eh
       [RMSNorm_e(Emb(id_{t+1})) ; RMSNorm_h(h^L_t)]``, ``z = Block_L(x)``
       (an attention + expert block of its own weights), ``logits1_t =
       Head(RMSNorm_s(z_t))`` predicts ``id_{t+2}``; ``Emb`` and ``Head``
       are the main model's.

``logits = Head(RMSNorm(h^L))``; ``loss = CE(logits_t, id_{t+1}) over t <=
S - 2 + mtp_loss_weight * CE(logits1_t, id_{t+2}) over t <= S - 3``, each a
mean over its own positions.

Departures from the source, each for a reason:

* The module runs over all ``S`` positions, the last one fed ``id_0`` (a
  roll of the ids), and that position is left out of its loss: attention is
  causal, so no earlier position sees it, and the positions stay a multiple
  of the blocks below.
* Attention is walked one head at a time and a block of query rows at a
  time, each recomputed in the backward pass, so that 8,192 x 8,192 scores
  of 32 heads are never held; every held expert is applied to every token
  of a block and weighted by the router's weight or zero; head and losses
  walk the positions in blocks. Each block of the stack is recomputed
  (``jax.checkpoint``) and sequences are walked one at a time, so that
  three steps at the timed size fit beside 16 bytes a parameter.
* ``mtp_loss_weight``, the order inside the concatenation, "before the
  final norm" and the sharing of ``Emb`` / ``Head`` are the DeepSeek-V3
  report's, not keys of ``config.json`` (configuration file, ``assumed``).
* No auxiliary balance loss, no update of the selection bias, no document
  boundaries: the step is a function of the seed.

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
from .nemotron_h import _by_token_blocks, _ein, _rms_norm, route


def layer_kinds(cfg):
    """``D`` (dense MLP) or ``E`` (routed experts) a main layer."""
    return "".join(
        "E" if i >= cfg["first_k_dense_replace"]
        and i % cfg["moe_layer_freq"] == 0 else "D"
        for i in range(cfg["num_hidden_layers"]))


def _block_shapes(cfg, i, dense):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    a, m = f"layers.{i}.self_attn.", f"layers.{i}.mlp."
    shapes = {
        f"layers.{i}.input_layernorm.weight": (d,),
        f"layers.{i}.post_attention_layernorm.weight": (d,),
        a + "q_a_proj.weight": (d, rq), a + "q_a_layernorm.weight": (rq,),
        a + "q_b_proj.weight": (rq, heads * (nope + rope)),
        a + "kv_a_proj_with_mqa.weight": (d, rkv + rope),
        a + "kv_a_layernorm.weight": (rkv,),
        a + "kv_b_proj.weight": (rkv, heads * (nope + dv)),
        a + "o_proj.weight": (heads * dv, d)}
    if dense:
        f = cfg["intermediate_size"]
        shapes.update({m + "gate_proj.weight": (d, f),
                       m + "up_proj.weight": (d, f),
                       m + "down_proj.weight": (f, d)})
    else:
        held, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
        fs = cfg["n_shared_experts"] * f
        shapes.update({
            m + "router.weight": (d, cfg["n_routed_experts_published"]),
            m + "experts_gate": (held, d, f), m + "experts_up": (held, d, f),
            m + "experts_down": (held, f, d),
            m + "shared_experts.gate_proj.weight": (d, fs),
            m + "shared_experts.up_proj.weight": (d, fs),
            m + "shared_experts.down_proj.weight": (fs, d)})
    return shapes


def param_shapes(cfg):
    d, v, depth = (cfg["hidden_size"], cfg["vocab_size"],
                   cfg["num_hidden_layers"])
    shapes = {"embed_tokens.weight": (v, d), "norm.weight": (d,),
              "lm_head.weight": (d, v)}
    for i, kind in enumerate(layer_kinds(cfg)):
        shapes.update(_block_shapes(cfg, i, kind == "D"))
    if cfg["num_nextn_predict_layers"]:
        shapes.update(_block_shapes(cfg, depth, False))
        shapes.update({f"layers.{depth}.enorm.weight": (d,),
                       f"layers.{depth}.hnorm.weight": (d,),
                       f"layers.{depth}.eh_proj.weight": (2 * d, d),
                       f"layers.{depth}.shared_head.norm.weight": (d,)})
    return shapes


def compared_leaves(cfg):
    return matrix_leaves(param_shapes(cfg))


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call:
    matrices normal(0, ``initializer_range``), no projection rescaled by
    the depth, unit norm scales (configuration file, ``assumed``)."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    def make(key):
        return {name: jnp.ones(shape, jnp.float32) if len(shape) == 1
                else std * jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
                for i, (name, shape) in enumerate(sorted(shapes.items()))}

    return jax.jit(make)(seed_key(seed))


def rotary(x, theta):
    """``R(x)`` of the module docstring; ``x`` [S, ..., D], the position
    along the first axis. Angles and rotation in float32, the frequencies
    made in float64 on the host."""
    s, d = x.shape[0], x.shape[-1]
    freq = np.asarray(float(theta) ** (-2.0 * np.arange(d // 2) / d),
                      np.float32)
    angle = jnp.arange(s, dtype=jnp.float32).reshape(
        (s,) + (1,) * (x.ndim - 1)) * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([even * cos - odd * sin,
                            odd * cos + even * sin], -1)


def mla_qkv(cfg, w, u, ein):
    """(q [S, H, d_qk], k [S, H, d_qk], v [S, H, d_v]) of one sequence
    ``u`` [S, hidden]."""
    s, heads, eps = u.shape[0], cfg["num_attention_heads"], \
        cfg["rms_norm_eps"]
    rkv, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    c_q = _rms_norm(ein("sd,dr->sr", u, w["q_a_proj.weight"]),
                    w["q_a_layernorm.weight"], eps)
    q = ein("sr,re->se", c_q, w["q_b_proj.weight"]).reshape(s, heads, -1)
    ckv = ein("sd,de->se", u, w["kv_a_proj_with_mqa.weight"])
    c_kv = _rms_norm(ckv[:, :rkv], w["kv_a_layernorm.weight"], eps)
    kv = ein("sr,re->se", c_kv, w["kv_b_proj.weight"]).reshape(s, heads, -1)
    theta = cfg["rope_theta"]
    k_r = rotary(ckv[:, rkv:], theta)                 # one for all heads
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_r[:, None], (s, heads, k_r.shape[-1]))], -1)
    return q, k, kv[..., nope:]


def _mla(cfg, w, u, ein):
    s = u.shape[0]
    q, k, v = mla_qkv(cfg, w, u, ein)
    at = jnp.arange(s)
    scale = 1.0 / math.sqrt(q.shape[-1])

    @jax.checkpoint     # one head at a time, a block of its query rows
    def one_head(args):
        q_h, k_h, v_h = args

        def rows(blk):
            q_b, at_b = blk
            scores = ein("qd,kd->qk", q_b, k_h) * scale
            probs = jax.nn.softmax(
                jnp.where(at_b[:, None] >= at[None, :], scores, -jnp.inf), -1)
            return ein("qk,kd->qd", probs, v_h)

        return _by_token_blocks(rows, (q_h, at))

    ctx = jax.lax.map(one_head, tuple(jnp.moveaxis(t, 1, 0)
                                      for t in (q, k, v)))
    return ein("se,ed->sd", jnp.moveaxis(ctx, 0, 1).reshape(s, -1),
               w["o_proj.weight"])


def _gated_mlp(u, gate, up, down, ein):
    return ein("tf,fd->td", jax.nn.silu(ein("td,df->tf", u, gate))
               * ein("td,df->tf", u, up), down)


def _dense(cfg, w, u, ein):
    return _by_token_blocks(
        lambda blk: _gated_mlp(blk, w["gate_proj.weight"],
                               w["up_proj.weight"], w["down_proj.weight"],
                               ein), u)


def _moe(cfg, w, u, ein, with_shared=True):
    """This share's part of the layer: the held experts' weighted outputs,
    plus the shared expert (``with_shared``: the share test counts it
    once)."""
    chosen, weights = route(cfg, u, w["router.weight"])
    first = cfg.get("first_expert_held", 0)

    def add_expert(out, held):      # a scan, so the program holds one body
        j, gate_w, up_w, down_w = held
        gate = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        return out + gate[:, None] * _gated_mlp(u, gate_w, up_w, down_w,
                                                ein), None

    out, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(u),
        (jnp.arange(cfg["n_routed_experts"]), w["experts_gate"],
         w["experts_up"], w["experts_down"]))
    if with_shared:
        out = out + _gated_mlp(u, w["shared_experts.gate_proj.weight"],
                               w["shared_experts.up_proj.weight"],
                               w["shared_experts.down_proj.weight"], ein)
    return out


def _moe_by_blocks(cfg, w, u, ein):
    return _by_token_blocks(lambda blk: _moe(cfg, w, blk, ein), u)


def _under(p, head):
    return {k[len(head):]: v for k, v in p.items() if k.startswith(head)}


def _block(cfg, p, i, dense, h, ein):
    """Block ``i`` of the state dict on the stream ``h`` [S, hidden],
    recomputed in the backward pass."""
    eps = cfg["rms_norm_eps"]
    ffn = _dense if dense else _moe_by_blocks

    @jax.checkpoint
    def run(h, w):
        h = h + _mla(cfg, _under(w, "self_attn."),
                     _rms_norm(h, w["input_layernorm.weight"], eps), ein)
        return h + ffn(cfg, _under(w, "mlp."),
                       _rms_norm(h, w["post_attention_layernorm.weight"],
                                 eps), ein)

    return run(h, _under(p, f"layers.{i}."))


def _hidden(cfg, p, row, ein):
    """The residual stream after the last main block, BEFORE the final
    norm, of one sequence ``row`` [S]."""
    h = p["embed_tokens.weight"][row]
    for i, kind in enumerate(layer_kinds(cfg)):
        h = _block(cfg, p, i, kind == "D", h, ein)
    return h


def _mtp_hidden(cfg, p, h, row, ein):
    """The prediction module's stream ``z`` (before its head's norm):
    position t combines ``h_t`` with the embedding of ``id_{t+1}``; the
    last position is fed ``id_0`` and nothing reads it."""
    depth, eps = cfg["num_hidden_layers"], cfg["rms_norm_eps"]
    w = _under(p, f"layers.{depth}.")
    e = p["embed_tokens.weight"][jnp.roll(row, -1)]
    x = ein("se,ed->sd", jnp.concatenate(
        [_rms_norm(e, w["enorm.weight"], eps),
         _rms_norm(h, w["hnorm.weight"], eps)], -1), w["eh_proj.weight"])
    return _block(cfg, p, depth, False, x, ein)


def _logits(cfg, p, h, norm, ein):
    return ein("sd,dv->sv", _rms_norm(h, norm, cfg["rms_norm_eps"]),
               p["lm_head.weight"])


def _mtp_norm(cfg, p):
    return p[f"layers.{cfg['num_hidden_layers']}.shared_head.norm.weight"]


def forward(cfg, p, ids, precision="float32"):
    """``(logits, mtp_logits)``, each [B, S, V], of ids [B, S], one
    sequence at a time."""
    ein = _ein(precision)

    def sequence(row):
        h = _hidden(cfg, p, row, ein)
        z = _mtp_hidden(cfg, p, h, row, ein)
        return (_logits(cfg, p, h, p["norm.weight"], ein),
                _logits(cfg, p, z, _mtp_norm(cfg, p), ein))

    return jax.lax.map(sequence, ids)


def loss_terms(cfg, p, batch, precision="float32"):
    """``(main, mtp)``: the mean next-token cross entropy over ``t <= S -
    2`` (position t's logits against token t + 1) and the module's mean
    cross entropy against token t + 2 over ``t <= S - 3``. Head and loss
    walk the positions in blocks, so that no whole [S, V] logits are
    held."""
    (ids,) = batch
    ein = _ein(precision)
    rows, seq = ids.shape
    at = jnp.arange(seq)

    def summed_ce(h, norm, labels, counts):
        def block(blk):
            h_b, labels_b, counts_b = blk
            logits = _logits(cfg, p, h_b, norm, ein)
            picked = jnp.take_along_axis(logits, labels_b[:, None], -1)[:, 0]
            return jnp.where(counts_b,
                             jax.nn.logsumexp(logits, -1) - picked, 0.0)
        return jnp.sum(_by_token_blocks(block, (h, labels, counts)))

    def sequence(row):
        h = _hidden(cfg, p, row, ein)
        z = _mtp_hidden(cfg, p, h, row, ein)
        return (summed_ce(h, p["norm.weight"], jnp.roll(row, -1),
                          at < seq - 1),
                summed_ce(z, _mtp_norm(cfg, p), jnp.roll(row, -2),
                          at < seq - 2))

    main, mtp = jax.lax.map(sequence, ids)
    return (jnp.sum(main) / (rows * (seq - 1)),
            jnp.sum(mtp) / (rows * (seq - 2)))


def loss_fn(cfg, p, batch, precision="float32"):
    main, mtp = loss_terms(cfg, p, batch, precision)
    return main + cfg["mtp_loss_weight"] * mtp


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
