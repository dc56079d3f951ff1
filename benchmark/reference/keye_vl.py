"""Plain reference for causal training of the language model of
Keye-VL-2.0-30B-A3B (``model_type: KeyeVL2``) in the sparse stage of the
DeepSeek-sparse-attention recipe: float32 ``jax.numpy``, no kernels,
nothing of paddle_tpu.

Model: the keys of the published ``config.json``; the layer of the
``qwen3_moe`` model code; the indexer, the selection and the indexer's
loss of DeepSeek-V3.2-Exp's report (section 2 and its continued
training), in front of grouped-query attention. ``eps = rms_norm_eps`` in
every norm, no bias but the indexer's layer norm's, the head untied.
``RMS(x) = x / sqrt(mean(x^2) + eps) * g``. One sequence of ``T`` rows;
row ``t`` has three position ids ``p_0(t), p_1(t), p_2(t)`` (temporal,
height, width) from the batch. Every layer:

    h = x + Attn(RMS_in(x))          y = h + MoE(RMS_post(h))

``Heads``  ``q, k, v = n W_q, n W_k, n W_v`` as ``num_attention_heads`` /
       ``num_key_value_heads`` heads of ``head_dim`` (D); ``q`` and ``k``
       through an RMS norm over the head (one scale a side); then the
       rotation, halves paired ``(x_j, x_{j + D/2})``, angle ``p_{a(j)}(t)
       theta ** (-2 j / D)`` with ``a(j)`` the chunk of ``mrope_section``
       that pair ``j`` lies in (contiguous chunks, in the order t, h, w).
       KV head ``g`` serves the query heads ``[g r, (g + 1) r)``.
``Indexer`` on ``n~ = stop_gradient(n)``: ``qI_t = n~_t W_qI`` as
       ``indexer_num_heads`` (Hi) heads of ``indexer_head_dim`` (Di);
       ``kI_t = LN(n~_t W_kI)`` (one key head; layer norm with scale and
       bias); both rotated by ``p_0`` alone over the whole Di (halves
       paired); ``w_t = n~_t W_w / sqrt(Hi Di)``. ``I(t, s) = sum_j w_tj
       relu(qI_tj . kI_s)`` for ``s <= t``, the heads added in order.
``Selection`` ``tau_t`` = the ``topk``-th largest of ``{I(t, s) : s <=
       t}`` (``-inf`` while ``t + 1 <= topk``); ``S_t = {s <= t : I(t, s)
       >= tau_t}``: a threshold, so ties keep every key at ``tau_t``.
``Attn``   ``a_th(s) = softmax over s in S_t of q_th . k_s,g(h) / sqrt(D)``;
       ``o_th = sum_s a_th(s) v_s,g(h)``; ``Attn = concat_h(o) W_o``.
``L_I``    of the layer: ``P_t(s) = mean_h stop_gradient(a_th(s))``; ``R_t =
       softmax over S_t of I(t, .)``; ``L_I = (1 / T) sum_t sum_{s in
       S_t} P_t(s) (log P_t(s) - log R_t(s))``, ``0 log 0 = 0``. It reaches
       ``W_qI, W_kI, LN, W_w`` alone; the language-model loss reaches none
       of them (the selection has no gradient).
``MoE``    the ``sdar_moe`` reference's: a float32 soft-max router over all
       published experts, top-k, renormalised; the held experts' gated
       MLPs (``sdar_moe.py: _moe`` is called as it is).

``logits = Head(RMS_final(h))``; ``L_LM = sum_t u_t CE(logits_t, id_{t+1})
/ sum_t u_t`` with ``u`` from the batch (0 at the last position); **the
step's loss is ``L_LM + sum_layers L_I``**, a mean over sequences too.

Departures, each for a reason: attention, index scores, the threshold (a
sort) and the indexer's loss are walked a block of query rows at a time,
all heads of the block together, each block recomputed in the backward
pass, so that ``T x T`` arrays are never held; head and loss walk the
positions in blocks; every block of the stack is recomputed. The batch's
position ids ``[3, T]`` are shared by its sequences.

Names are the program's (``sdar_moe.py``'s, and ``self_attn.indexer_q /
indexer_k / indexer_k_norm / indexer_w``). Optimizer: AdamW with decay on
every parameter (``bert_pretrain.py``).
"""
import math

import numpy as np

import jax
import jax.numpy as jnp

from . import sdar_moe
from .bert_pretrain import adamw_update
from .common import diff_norms, leaf_norms, matrix_leaves, seed_key
from .nemotron_h import _by_token_blocks, _ein, _rms_norm
from .sdar_moe import _moe, _under

ROW_BLOCK = 128     # query rows a block of the attention walk


def param_shapes(cfg):
    """``sdar_moe``'s leaves and the indexer's five a layer."""
    d, sa = cfg["hidden_size"], cfg["sa_config"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    shapes = sdar_moe.param_shapes(cfg)
    for i in range(cfg["num_hidden_layers"]):
        a = f"layers.{i}.self_attn."
        shapes.update({
            a + "indexer_q.weight": (d, hi * di),
            a + "indexer_k.weight": (d, di),
            a + "indexer_k_norm.weight": (di,),
            a + "indexer_k_norm.bias": (di,),
            a + "indexer_w.weight": (d, hi)})
    return shapes


def compared_leaves(cfg):
    """The matrices, the indexer's three among them, and the scale of its
    key norm: the one norm whose gradient is the indexer's loss's alone."""
    shapes = param_shapes(cfg)
    return sorted(matrix_leaves(shapes) + [
        k for k in shapes if k.endswith("indexer_k_norm.weight")])


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call: norm
    scales 1 and the layer norm's bias 0, matrices normal(0,
    ``initializer_range``), the embedding normal(0,
    ``embedding_initializer_range``) and, with
    ``rescale_prenorm_residual``, ``o_proj`` and ``experts_down`` divided
    by ``sqrt(2 * num_hidden_layers)`` (the ``sdar_moe`` configuration's
    ``assumed.weights`` says why)."""
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
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if len(shape) == 1:
                out[name] = (jnp.zeros if name.endswith(".bias")
                             else jnp.ones)(shape, jnp.float32)
            else:
                out[name] = scale(name) * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def rotary(x, positions, theta, sections=None):
    """``x`` [S, ..., D] with halves paired; ``positions`` [S], or [axes,
    S] with ``sections``: pair ``j`` turns by the axis of its chunk.
    Angles and rotation in float32, the frequencies made in float64 on the
    host."""
    d = x.shape[-1]
    freq = np.asarray(float(theta) ** (-2.0 * np.arange(d // 2) / d),
                      np.float32)
    p = positions.astype(jnp.float32)
    if sections is None:
        p = p[:, None]
    else:
        p = p[np.repeat(np.arange(len(sections)), sections)].T
    angle = (p * freq).reshape((p.shape[0],) + (1,) * (x.ndim - 2)
                               + (d // 2,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def indexer(cfg, w, n, positions, ein):
    """(qI [T, Hi, Di], kI [T, Di], w [T, Hi]) of the normed rows ``n``,
    which get no gradient from them."""
    sa, eps, theta = cfg["sa_config"], cfg["rms_norm_eps"], cfg["rope_theta"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    n = jax.lax.stop_gradient(n)
    qi = ein("sd,de->se", n, w["indexer_q.weight"]).reshape(-1, hi, di)
    ki = _layer_norm(ein("sd,de->se", n, w["indexer_k.weight"]),
                     w["indexer_k_norm.weight"], w["indexer_k_norm.bias"],
                     eps)
    return (rotary(qi, positions[0], theta), rotary(ki, positions[0], theta),
            ein("sd,de->se", n, w["indexer_w.weight"]) / math.sqrt(hi * di))


def index_scores(qi, ki, wi, ein):
    """``I`` [R, K] of rows ``qi`` [R, Hi, Di], ``wi`` [R, Hi] against
    keys ``ki`` [K, Di], the heads added in order; ``-0.0`` made 0."""
    acc = jnp.zeros((qi.shape[0], ki.shape[0]), jnp.float32)
    for j in range(qi.shape[1]):
        acc = acc + wi[:, j:j + 1] * jax.nn.relu(
            ein("rd,kd->rk", qi[:, j], ki))
    return acc + 0.0


def selection(scores, at, topk):
    """bool [R, K]: the keys rows ``at`` [R] select of their causal
    ``scores`` [R, K] (module docstring); no gradient."""
    scores = jax.lax.stop_gradient(scores)
    causal = jnp.arange(scores.shape[1])[None, :] <= at[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    if topk >= scores.shape[1]:
        return causal
    kth = -jnp.sort(-scores, axis=-1)[:, topk - 1]
    tau = jnp.where(at + 1 <= topk, -jnp.inf, kth)
    return causal & (scores >= tau[:, None])


def qkv(cfg, w, n, positions, ein):
    """(q [T, heads, D], k and v [T, kv heads, D])."""
    rows, hd = n.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    sections = tuple(cfg["rope_scaling"]["mrope_section"])
    q = ein("sd,de->se", n, w["q_proj.weight"]).reshape(rows, heads, hd)
    k = ein("sd,de->se", n, w["k_proj.weight"]).reshape(rows, kv, hd)
    v = ein("sd,de->se", n, w["v_proj.weight"]).reshape(rows, kv, hd)
    q = rotary(_rms_norm(q, w["q_norm.weight"], eps), positions, theta,
               sections)
    k = rotary(_rms_norm(k, w["k_norm.weight"], eps), positions, theta,
               sections)
    return q, k, v


def _by_row_blocks(fn, rows):
    """``fn(block of rows) -> (per-row array, scalar)`` over ``rows`` (a
    tuple of arrays [T, ...]) a ROW_BLOCK at a time, each recomputed in the
    backward pass: (the rows' results [T, ...], the scalars' sum)."""
    t = rows[0].shape[0]
    if t <= ROW_BLOCK or t % ROW_BLOCK:
        return fn(rows)
    blocks = jax.tree.map(
        lambda a: a.reshape(t // ROW_BLOCK, ROW_BLOCK, *a.shape[1:]), rows)
    out, parts = jax.lax.map(jax.checkpoint(fn), blocks)
    return out.reshape(t, *out.shape[2:]), jnp.sum(parts)


def attention(cfg, w, n, positions, ein, select=True):
    """(``Attn(n)`` [T, hidden], the layer's ``L_I``). ``select`` False
    leaves the selection out (dense causal attention, ``L_I`` over every
    causal key): what a broken timed path is compared with in the tests."""
    t, hd = n.shape[0], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    topk = cfg["sa_config"]["topk"] if select else t
    q, k, v = qkv(cfg, w, n, positions, ein)
    qi, ki, wi = indexer(cfg, w, n, positions, ein)
    q = q.reshape(t, kv, heads // kv, hd)

    def rows(blk):
        q_b, qi_b, wi_b, at = blk
        scores = index_scores(qi_b, ki, wi_b, ein)
        keep = selection(scores, at, topk)
        s = ein("qgrd,kgd->grqk", q_b, k) / math.sqrt(hd)
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
        o = ein("grqk,kgd->qgrd", a, v).reshape(-1, heads * hd)
        target = jax.lax.stop_gradient(jnp.mean(a, (0, 1)))
        log_r = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), -1)
        seen = target > 0
        part = jnp.sum(jnp.where(
            seen, target * (jnp.log(jnp.where(seen, target, 1.0))
                            - jnp.where(seen, log_r, 0.0)), 0.0))
        return o, part

    ctx, loss = _by_row_blocks(rows, (q, qi, wi, jnp.arange(t)))
    return ein("se,ed->sd", ctx, w["o_proj.weight"]), loss / t


def _hidden(cfg, p, ids, positions, ein):
    """(the residual stream after the last block [T, hidden], the layers'
    ``L_I`` added up) of one sequence."""
    eps = cfg["rms_norm_eps"]
    h = p["embed_tokens.weight"][ids]
    indexer_loss = jnp.zeros((), jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        @jax.checkpoint
        def block(h, w):
            a, part = attention(
                cfg, _under(w, "self_attn."),
                _rms_norm(h, w["input_layernorm.weight"], eps), positions,
                ein)
            h = h + a
            u = _rms_norm(h, w["post_attention_layernorm.weight"], eps)
            return h + _by_token_blocks(
                lambda blk: _moe(cfg, _under(w, "mlp."), blk, ein), u), part
        h, part = block(h, _under(p, f"layers.{i}."))
        indexer_loss = indexer_loss + part
    return h, indexer_loss


def _logits(cfg, p, h, ein):
    return ein("sd,dv->sv", _rms_norm(h, p["norm.weight"],
                                      cfg["rms_norm_eps"]),
               p["lm_head.weight"])


def forward(cfg, p, ids, position_ids, precision="float32"):
    """(logits [B, T, V], the indexers' losses added up and averaged over
    the sequences), one sequence at a time."""
    ein = _ein(precision)

    def sequence(row):
        h, indexer_loss = _hidden(cfg, p, row, position_ids, ein)
        return _logits(cfg, p, h, ein), indexer_loss

    logits, indexer_loss = jax.lax.map(sequence, ids)
    return logits, jnp.mean(indexer_loss)


def losses(cfg, p, batch, precision="float32"):
    """``(L_LM, sum of L_I)``; head and loss walk the positions in blocks,
    so that no whole [T, V] logits are held."""
    ids, position_ids, weights = batch
    ein = _ein(precision)
    weights = weights.astype(jnp.float32)

    def sequence(args):
        row, u = args
        h, indexer_loss = _hidden(cfg, p, row, position_ids, ein)
        labels = jnp.concatenate([row[1:], jnp.zeros((1,), row.dtype)])

        def block(blk):
            h_b, labels_b, u_b = blk
            logits = _logits(cfg, p, h_b, ein)
            picked = jnp.take_along_axis(logits, labels_b[:, None], -1)[:, 0]
            return u_b * (jax.nn.logsumexp(logits, -1) - picked)

        return jnp.sum(_by_token_blocks(block, (h, labels, u))), indexer_loss

    lm, indexer_loss = jax.lax.map(sequence, (ids, weights))
    return jnp.sum(lm) / jnp.sum(weights), jnp.mean(indexer_loss)


def loss_fn(cfg, p, batch, precision="float32"):
    """The step's loss: ``L_LM + sum of L_I``."""
    lm, indexer_loss = losses(cfg, p, batch, precision)
    return lm + indexer_loss


def train(cfg, hyper, seed, batches, precision="float32"):
    """Follow ``len(batches)`` optimizer steps from the seed's weights and
    return what ``common.follow`` returns, as ``sdar_moe.train`` (donated
    state, the seed's weights made a second time for the change)."""
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
        all_losses, first = [], None
        for t, batch in enumerate(batches, 1):
            p, m, v, loss, norms = jstep(
                p, m, v, jnp.float32(t), tuple(jnp.asarray(a) for a in batch))
            all_losses.append(float(loss))
            if first is None:
                first = jax.device_get(norms)
        del m, v
        delta = jax.device_get(jax.jit(diff_norms)(p, init_weights(cfg, seed)))
    return {"loss": all_losses,
            "first_grad_norm": {k: float(x) for k, x in first.items()},
            "delta_norm": {k: float(x) for k, x in delta.items()}}
