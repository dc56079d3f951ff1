"""Plain reference for causal pre-training of a ``nemotron_h`` hybrid:
float32 ``jax.numpy``, no kernels, nothing of paddle_tpu.

Model: the ``nemotron_h`` modelling code that the published ``config.json``
names (``model_type: nemotron_h``). A residual stream of pre-norm blocks,
``h <- h + Mixer(RMSNorm(h))``, one mixer a block, chosen by
``hybrid_override_pattern``:

``M``  Mamba-2 mixer (Dao & Gu, arXiv:2405.21060): ``[z | xBC | dt] = u W_in``;
       a causal depthwise convolution of width ``conv_kernel`` and SiLU over
       ``xBC``, split into ``x`` (heads x head_dim), ``B`` and ``C`` (groups x
       state; head h reads group h // (heads / groups)); ``dt = softplus(dt +
       dt_bias)``, ``A = -exp(A_log)``; per head the recurrence ``H_t =
       exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``; the
       gate goes on before the grouped RMS norm, ``y <- GroupRMSNorm(y *
       silu(z)) * w``; ``out = y W_out``.
``*``  grouped-query attention, causal, no bias and no rotary embedding (the
       ``nemotron_h`` attention applies none); KV head j serves the query
       heads ``[j r, (j + 1) r)``, ``r = heads / kv_heads``.
``E``  mixture of experts: ``s = sigmoid(u W_r)`` over all published experts,
       in float32; chosen = top-k of ``s + b`` (``b`` the selection bias, a
       buffer, zero here); weights ``scale * s_i / (sum_chosen s + 1e-20)``;
       experts ``W_down relu(W_up u)^2``; plus one shared expert of the same
       form. **The share**: ``cfg["n_routed_experts"]`` counts the experts
       held here, ``first_expert_held .. + n_routed_experts`` of the
       published ``n_routed_experts_published``; the router keeps the
       published width and what the absent experts would add is left out.

After the last block ``logits = RMSNorm_f(h) W_head`` (untied), and the loss
is the mean next-token cross entropy over the predicted positions.

Departures from the source, each for a reason:

* The recurrence is evaluated in its chunked closed form and not step by
  step: a time-step scan would keep ``seq`` states of heads x head_dim x
  state for the backward pass (8,192 x 64 x 64 x 128 floats). Within a
  chunk of ``chunk_size`` positions the output is the masked decay matrix
  ``L[t, s] = exp(sum_{s < r <= t} dt_r A)`` applied to ``(C_t . B_s) dt_s
  x_s``; between chunks one state is carried by a scan over the chunks. The
  sums in ``L`` are taken as masked cumulative sums of the individual terms
  (never as differences of two long sums), and the carried state by a
  sequential scan: both exact. A length that is no multiple of the chunk is
  padded with ``dt = 0`` positions, which neither move the state nor are
  read.
* Every expert held here is applied to every token and weighted by the
  router's weight or zero: the plain form of "the tokens routed to it".
* Each block is recomputed in the backward pass (``jax.checkpoint``) and
  sequences are walked one at a time, so that three steps at the timed size
  fit beside 16 bytes a parameter of weights, gradient and AdamW state.
* No auxiliary balance loss, no update of the selection bias, no document
  boundaries: the step is a function of the seed (configuration file,
  ``assumed``).

Names and layouts are the served model's state-dict names, so that the same
seeded arrays can be loaded there: a linear weight is ``[in, out]``; the
depthwise taps are ``[channels, conv_kernel]`` (tap ``k - 1`` multiplies the
current position); expert weights are stacked ``[held, in, out]``.

Optimizer: AdamW with decay on every parameter (``bert_pretrain.py``).
"""
import math

import jax
import jax.numpy as jnp

from .bert_pretrain import adamw_update
from .common import (bilinear, diff_norms, leaf_norms, matrix_leaves,
                     seed_key)

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def pattern(cfg):
    p = cfg["hybrid_override_pattern"]
    if len(p) != cfg["num_hidden_layers"] or set(p) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern {p!r} does not describe "
                         f"{cfg['num_hidden_layers']} layers of {set(KINDS)}")
    return p


def mamba_sizes(cfg):
    """(heads, head_dim, inner width, groups, state, conv width)."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return h, p, h * p, g, n, h * p + 2 * g * n


def param_shapes(cfg):
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p, d_in, g, n, conv = mamba_sizes(cfg)
    qd = cfg["num_attention_heads"] * cfg["head_dim"]
    kvd = cfg["num_key_value_heads"] * cfg["head_dim"]
    held, routed = cfg["n_routed_experts"], cfg["n_routed_experts_published"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    shapes = {"embeddings.weight": (v, d), "norm_f.weight": (d,),
              "lm_head.weight": (d, v)}
    for i, kind in enumerate(pattern(cfg)):
        m = f"layers.{i}.mixer."
        shapes[f"layers.{i}.norm.weight"] = (d,)
        if kind == "M":
            shapes.update({
                m + "in_proj.weight": (d, d_in + conv + h),
                m + "conv_weight": (conv, cfg["conv_kernel"]),
                m + "conv_bias": (conv,),
                m + "dt_bias": (h,), m + "A_log": (h,), m + "D": (h,),
                m + "norm.weight": (d_in,),
                m + "out_proj.weight": (d_in, d)})
        elif kind == "*":
            shapes.update({
                m + "q_proj.weight": (d, qd), m + "k_proj.weight": (d, kvd),
                m + "v_proj.weight": (d, kvd), m + "o_proj.weight": (qd, d)})
        else:
            shapes.update({
                m + "router.weight": (d, routed),
                m + "experts_up": (held, d, f),
                m + "experts_down": (held, f, d),
                m + "shared_up.weight": (d, fs),
                m + "shared_down.weight": (fs, d)})
    return shapes


def compared_leaves(cfg):
    return matrix_leaves(param_shapes(cfg))


_RESIDUAL_OUT = ("out_proj.weight", "o_proj.weight", "experts_down",
                 "shared_down.weight")


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call
    (configuration file, ``assumed.weights``)."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]
    depth = cfg["num_hidden_layers"]
    lo, hi, floor = (cfg["time_step_min"], cfg["time_step_max"],
                     cfg["time_step_floor"])
    tap = 1.0 / math.sqrt(cfg["conv_kernel"])

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.endswith("norm.weight") or name == "norm_f.weight" \
                    or name.endswith(".D"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith("A_log"):
                out[name] = jnp.log(jax.random.uniform(
                    k, shape, jnp.float32, 1.0, 16.0))
            elif name.endswith("dt_bias"):
                dt = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, math.log(lo), math.log(hi)))
                dt = jnp.maximum(dt, floor)
                out[name] = dt + jnp.log(-jnp.expm1(-dt))   # softplus^-1
            elif "conv_" in name:
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -tap, tap)
            else:
                scale = std / math.sqrt(depth) \
                    if name.endswith(_RESIDUAL_OUT) else std
                out[name] = scale * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def _rms_norm(x, w, eps, groups=1):
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return x.reshape(shape) * w


def _segsum(a):
    """``out[..., t, s] = sum_{s < r <= t} a[..., r]`` for ``s <= t`` and
    ``-inf`` above the diagonal, every sum taken term by term."""
    n = a.shape[-1]
    rows = jnp.arange(n)[:, None]
    cols = jnp.arange(n)[None, :]
    terms = jnp.where(rows > cols, a[..., :, None], 0.0)   # [r, s]: r > s
    sums = jnp.cumsum(terms, axis=-2)                      # over r <= t
    return jnp.where(rows >= cols, sums, -jnp.inf)


def ssm_step_by_step(x, dt, a, b_in, c_in, d_skip):
    """The recurrence as written, one position at a time: what the chunked
    form has to equal. ``x`` [S, H, P]; ``dt`` [S, H]; ``a``, ``d_skip``
    [H]; ``b_in``, ``c_in`` [S, H, N] (already one a head)."""
    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = state * jnp.exp(dt_t * a)[:, None, None] \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    h, p, n = x.shape[1], x.shape[2], b_in.shape[-1]
    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (x, dt, b_in, c_in))
    return y + d_skip[:, None] * x


def ssm_chunked(x, dt, a, b_in, c_in, d_skip, chunk, ein):
    """The same in the chunked closed form (module docstring). ``b_in``,
    ``c_in`` are [S, G, N], one a group."""
    s, h, p = x.shape
    g, n = b_in.shape[1:]
    r = h // g
    pad = -s % chunk
    if pad:
        x, dt, b_in, c_in = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                             for t in (x, dt, b_in, c_in))
    k = (s + pad) // chunk
    xc = x.reshape(k, chunk, g, r, p)
    dtc = dt.reshape(k, chunk, g, r)
    bc = b_in.reshape(k, chunk, g, n)
    cc = c_in.reshape(k, chunk, g, n)
    ac = dtc * a.reshape(g, r)                             # log decay a step

    def one_chunk(state, inp):
        x_k, dt_k, b_k, c_k, a_k = inp
        a_k = jnp.moveaxis(a_k, 0, -1)                     # [G, R, L]
        decay = jnp.exp(_segsum(a_k))                      # [G, R, L, L]
        cum = jnp.cumsum(a_k, -1)                          # [G, R, L]
        xdt = x_k * dt_k[..., None]                        # [L, G, R, P]
        scores = ein("tgn,sgn->gts", c_k, b_k)             # [G, L, L]
        y = ein("grts,sgrp->tgrp", scores[:, None] * decay, xdt)
        y = y + ein("tgn,grpn->tgrp", c_k, state) \
            * jnp.moveaxis(jnp.exp(cum), -1, 0)[..., None]
        left = jnp.exp(cum[..., -1:] - cum)                # to the chunk end
        state = state * jnp.exp(cum[..., -1])[..., None, None] \
            + ein("sgn,sgrp->grpn", b_k,
                  xdt * jnp.moveaxis(left, -1, 0)[..., None])
        return state, y

    _, y = jax.lax.scan(one_chunk, jnp.zeros((g, r, p, n), jnp.float32),
                        (xc, dtc, bc, cc, ac))
    y = y.reshape(k * chunk, h, p)[:s]
    return y + d_skip[:, None] * x[:s]


def _mamba(cfg, w, u, ein):
    h, p, d_in, g, n, conv = mamba_sizes(cfg)
    s = u.shape[0]
    zxbcdt = ein("sd,de->se", u, w["in_proj.weight"])
    z, xbc, dt = jnp.split(zxbcdt, [d_in, d_in + conv], axis=-1)
    taps = cfg["conv_kernel"]
    padded = jnp.pad(xbc, [(taps - 1, 0), (0, 0)])
    xbc = sum(padded[j:j + s] * w["conv_weight"][:, j] for j in range(taps))
    xbc = jax.nn.silu(xbc + w["conv_bias"])
    x, b_in, c_in = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    y = ssm_chunked(x.reshape(s, h, p),
                    jax.nn.softplus(dt + w["dt_bias"]),
                    -jnp.exp(w["A_log"]), b_in.reshape(s, g, n),
                    c_in.reshape(s, g, n), w["D"], cfg["chunk_size"], ein)
    y = _rms_norm(y.reshape(s, d_in) * jax.nn.silu(z), w["norm.weight"],
                  cfg["layer_norm_epsilon"], groups=g)
    return ein("se,ed->sd", y, w["out_proj.weight"])


def _attention(cfg, w, u, ein):
    s = u.shape[0]
    heads, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    q = ein("sd,de->se", u, w["q_proj.weight"]).reshape(s, heads, dh)
    k = ein("sd,de->se", u, w["k_proj.weight"]).reshape(s, kv, dh)
    v = ein("sd,de->se", u, w["v_proj.weight"]).reshape(s, kv, dh)
    at = jnp.arange(s)

    @jax.checkpoint     # one query head at a time, a block of its rows
    def one_head(args):
        q_h, k_j, v_j = args

        def rows(blk):
            q_b, at_b = blk
            scores = ein("qd,kd->qk", q_b, k_j) / math.sqrt(dh)
            probs = jax.nn.softmax(
                jnp.where(at_b[:, None] >= at[None, :], scores, -jnp.inf), -1)
            return ein("qk,kd->qd", probs, v_j)

        return _by_token_blocks(rows, (q_h, at))

    serves = jnp.arange(heads) // (heads // kv)      # query head -> KV head
    ctx = jax.lax.map(one_head, (jnp.moveaxis(q, 1, 0),
                                 jnp.moveaxis(k, 1, 0)[serves],
                                 jnp.moveaxis(v, 1, 0)[serves]))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(s, heads * dh)
    return ein("se,ed->sd", ctx, w["o_proj.weight"])


def route(cfg, u, router_weight, bias=None):
    """(chosen experts [T, k], their weights [T, k]) of the published
    router, over all published experts, in float32."""
    s = jax.nn.sigmoid(jnp.einsum(
        "td,de->te", u.astype(jnp.float32), router_weight.astype(jnp.float32),
        precision="highest"))
    _, chosen = jax.lax.top_k(s if bias is None else s + bias,
                              cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, -1)
    weights = cfg["routed_scaling_factor"] * picked \
        / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    return chosen, weights


def _relu2_mlp(u, up, down, ein):
    return ein("tf,fd->td",
               jnp.square(jax.nn.relu(ein("td,df->tf", u, up))), down)


def _moe(cfg, w, u, ein, with_shared=True):
    """This share's part of the layer: the held experts' weighted outputs,
    plus the shared expert (``with_shared``: the share test counts it
    once)."""
    chosen, weights = route(cfg, u, w["router.weight"])
    first = cfg.get("first_expert_held", 0)
    out = jnp.zeros_like(u)
    for j in range(cfg["n_routed_experts"]):
        gate = jnp.sum(jnp.where(chosen == first + j, weights, 0.0), -1)
        out = out + gate[:, None] * _relu2_mlp(
            u, w["experts_up"][j], w["experts_down"][j], ein)
    if with_shared:
        out = out + _relu2_mlp(u, w["shared_up.weight"],
                               w["shared_down.weight"], ein)
    return out


TOKEN_BLOCK = 1024      # positions a block of the token-wise parts


def _by_token_blocks(fn, u):
    """``fn`` over ``u`` (an array [S, ...] or a tuple of such) a block of
    positions at a time, each block recomputed in the backward pass;
    ``fn`` maps positions independently. A length that does not split
    into whole blocks is taken whole."""
    s = jax.tree.leaves(u)[0].shape[0]
    if s <= TOKEN_BLOCK or s % TOKEN_BLOCK:
        return fn(u)
    blocks = jax.tree.map(
        lambda t: t.reshape(s // TOKEN_BLOCK, TOKEN_BLOCK, *t.shape[1:]), u)
    out = jax.lax.map(jax.checkpoint(fn), blocks)
    return out.reshape(s, *out.shape[2:])


def _moe_by_blocks(cfg, w, u, ein):
    return _by_token_blocks(lambda blk: _moe(cfg, w, blk, ein), u)


_MIXERS = {"M": _mamba, "*": _attention, "E": _moe_by_blocks}


def _layer_weights(p, i):
    head = f"layers.{i}.mixer."
    return {k[len(head):]: v for k, v in p.items() if k.startswith(head)}


def _ein(precision):
    def ein(spec, a, b):
        return bilinear(lambda x, y: jnp.einsum(spec, x, y), precision)(a, b)
    return ein


def _hidden(cfg, p, row, ein):
    """The residual stream after the last block, of one sequence [S]."""
    eps = cfg["layer_norm_epsilon"]
    h = p["embeddings.weight"][row]
    for i, kind in enumerate(pattern(cfg)):
        @jax.checkpoint
        def block(h, w, norm, kind=kind):
            return h + _MIXERS[kind](cfg, w, _rms_norm(h, norm, eps), ein)
        h = block(h, _layer_weights(p, i), p[f"layers.{i}.norm.weight"])
    return h


def _logits(cfg, p, h, ein):
    return ein("sd,dv->sv", _rms_norm(h, p["norm_f.weight"],
                                      cfg["layer_norm_epsilon"]),
               p["lm_head.weight"])


def forward(cfg, p, ids, precision="float32"):
    """Logits [B, S, V] of ids [B, S], one sequence at a time."""
    ein = _ein(precision)
    return jax.lax.map(
        lambda row: _logits(cfg, p, _hidden(cfg, p, row, ein), ein), ids)


def loss_fn(cfg, p, batch, precision="float32"):
    """Mean next-token cross entropy over the predicted positions: position
    t's logits against token t + 1, the last position predicting nothing.
    The head and the loss walk the positions in blocks, so that the whole
    [S, V] logits are never held."""
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
    the last step. The loop is this file's own: parameters and moments are
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
