"""Plain reference for causal pre-training of ``phi4flash``
(Phi-4-mini-flash-reasoning: a decoder-hybrid-decoder — Mamba-1 and
windowed differential attention in turn, one full attention layer, then
gated memory units and cross attention that read ONE layer's scan memory
and ONE layer's keys and values; a tied head): float32 ``jax.numpy``, no
kernels, nothing of paddle_tpu.

Model: the keys of the published ``config.json`` (``model_type:
phi4flash``); the layers of arXiv:2507.06607 and of the model code
published beside the config. ``LN(x) = (x - mean) / sqrt(var + eps) * g +
b`` everywhere (``layer_norm_eps``), no dropout, NO position embedding. For
held layer ``i`` (the source's ``l = first_layer + i`` of ``N =
num_hidden_layers_published``) with input ``x`` [T, d] of one sequence

    h = x + Mixer_l(LN(x))      y = h + W_2 (silu(W_g m) * W_u m),  m = LN(h)

(``gate_proj | up_proj`` are the two halves of the source's fused ``fc1``,
``down_proj`` its ``fc2``; no bias.) The mixer by ``layer_plan[l]``:

``mamba`` (``l`` even, ``l <= N/2``): ``[x | z] = n W_in``; ``x <-
    silu(conv_K(x) + b_conv)`` (causal, depthwise, ``mamba_d_conv`` taps,
    tap ``K - 1`` on the current position); ``[dt_r | B | C] = x W_x``;
    ``Delta = softplus(dt_r W_dt + b_dt)`` [T, inner]; ``A = -exp(A_log)``
    [inner, state]; ``H_t = exp(Delta_t A) * H_{t-1} + (Delta_t x_t)
    B_t^T``; ``y_t = H_t C_t + D x_t``; out ``= (y * silu(z)) W_out``.
    **Layer N/2 also hands on ``M = y``, taken before the gate.**
``window_attention`` (``l`` odd, ``l < N/2``), ``full_attention`` (``l =
    N/2 + 1``): ``q = n W_q + b_q`` (``num_attention_heads`` heads of ``D =
    d / heads``), ``k``, ``v`` likewise (``num_key_value_heads``). Heads
    pair up as ``(2j, 2j + 1)``; query pair ``j`` reads key/value pair ``g
    = j // (heads / kv heads)``: ``A1 = softmax(mask(q_2j k_2g^T /
    sqrt(D)))``, ``A2 = softmax(mask(q_2j+1 k_2g+1^T / sqrt(D)))``, ``V =
    [v_2g | v_2g+1]``, ``o_j = (1 - lambda_init) RMS_2D(A1 V - lambda A2
    V) * g_sub`` (ONE ``g_sub`` [2 D] a layer), ``lambda = exp(lq1 . lk1)
    - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3
    l)`` by the SOURCE index; the pairs side by side through ``W_o + b_o``.
    The mask is causal, under a window of ``sliding_window`` keys (a row
    sees itself and the ``W - 1`` before it) in the window layers. **The
    full attention layer also hands on its ``k`` and ``v``.**
``memory_unit`` (``l`` even, ``l >= N/2 + 2``): ``(silu(n W_1) * M) W_2``.
``cross_attention`` (``l`` odd, ``l >= N/2 + 3``): queries only, the
    differential attention above, full causal, over layer ``N/2 + 1``'s
    ``k`` and ``v``; its own ``lambda`` vectors, ``g_sub``, ``W_o``.

After the last layer ``LN_final`` and ``logits = h E^T`` with ``E`` the
embedding: ONE leaf, whose gradient is the look-up's plus the head's. The
loss is the mean next-token cross entropy over the predicted positions.

Departures from the source, each for a reason:

* The recurrence is a ``lax.scan`` over the positions, a chunk of them
  (``SCAN_CHUNK``) recomputed in the backward pass: 8,192 states of 5,120
  x 16 floats would be 2.7 GB a layer. Attention is walked one pair of
  heads and a block of query rows at a time, each map's soft-max
  materialised, recomputed in the backward pass; head and loss walk the
  positions in blocks; each block of the stack is recomputed and sequences
  are walked one at a time, so that three steps at the timed size fit
  beside 16 bytes a parameter.
* ``Wqkv`` and ``fc1`` are held as their parts (above): the same numbers.
* No document boundaries: the step is a function of the seed.

Names are the program's (``paddle_tpu/models/phi4_flash.py``), so that the
same seeded arrays load there: a linear weight is ``[in, out]``.

Optimizer: AdamW with decay on every parameter (``bert_pretrain.py``).
"""
import math

import jax
import jax.numpy as jnp

from .bert_pretrain import adamw_update
from .common import diff_norms, leaf_norms, matrix_leaves, seed_key
from .keye_vl import _layer_norm
from .nemotron_h import _by_token_blocks, _ein
from .sdar_moe import _gated_mlp, _under

KINDS = ("mamba", "window_attention", "full_attention", "memory_unit",
         "cross_attention")
SCAN_CHUNK = 256        # positions of the recurrence recomputed together


def published_plan(layers=32, mb_per_layer=2):
    half = layers // 2
    return [("mamba" if l <= half else "memory_unit")
            if l % mb_per_layer == 0 else
            "window_attention" if l < half else
            "full_attention" if l == half + 1 else "cross_attention"
            for l in range(layers)]


def layer_kinds(cfg):
    """[(kind, source index, whether it hands something on)] a held layer."""
    first, layers = cfg.get("first_layer", 0), cfg["num_hidden_layers"]
    plan = cfg.get("layer_plan") or published_plan(
        cfg.get("num_hidden_layers_published", first + layers),
        cfg.get("mb_per_layer", 2))
    kinds = list(plan[first:first + layers])
    if len(kinds) != layers or set(kinds) - set(KINDS):
        raise ValueError(f"layer_plan {plan!r} does not describe {layers} "
                         f"layers from {first} on")

    def giver(kind, reader):
        if reader not in plan:
            return None
        return max(l for l in range(list(plan).index(reader))
                   if plan[l] == kind)

    gives = (giver("mamba", "memory_unit"),
             giver("full_attention", "cross_attention"))
    return [(kind, first + i, first + i in gives)
            for i, kind in enumerate(kinds)]


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def mamba_sizes(cfg):
    """(inner, state, taps, dt_rank)."""
    d = cfg["hidden_size"]
    return (cfg.get("mamba_expand", 2) * d, cfg.get("mamba_d_state", 16),
            cfg.get("mamba_d_conv", 4),
            cfg.get("mamba_dt_rank") or -(-d // 16))


def param_shapes(cfg):
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    inner, n, taps, r = mamba_sizes(cfg)
    shapes = {"embed_tokens.weight": (v, d), "final_layernorm.weight": (d,),
              "final_layernorm.bias": (d,)}
    for i, (kind, _, _) in enumerate(layer_kinds(cfg)):
        at = f"layers.{i}."
        for norm in ("input_layernorm", "post_attention_layernorm"):
            shapes.update({at + norm + ".weight": (d,),
                           at + norm + ".bias": (d,)})
        m = at + "mixer."
        if kind == "mamba":
            shapes.update({
                m + "in_proj.weight": (d, 2 * inner),
                m + "conv_weight": (inner, taps), m + "conv_bias": (inner,),
                m + "x_proj.weight": (inner, r + 2 * n),
                m + "dt_proj.weight": (r, inner),
                m + "dt_proj.bias": (inner,),
                m + "A_log": (inner, n), m + "D": (inner,),
                m + "out_proj.weight": (inner, d)})
        elif kind == "memory_unit":
            shapes.update({m + "in_proj.weight": (d, inner),
                           m + "out_proj.weight": (inner, d)})
        else:
            shapes.update({
                m + "q_proj.weight": (d, heads * hd),
                m + "q_proj.bias": (heads * hd,),
                m + "o_proj.weight": (heads * hd, d), m + "o_proj.bias": (d,),
                m + "subln.weight": (2 * hd,)})
            shapes.update({m + f"lambda_{x}": (hd,)
                           for x in ("q1", "k1", "q2", "k2")})
            if kind != "cross_attention":
                shapes.update({m + "k_proj.weight": (d, kv * hd),
                               m + "k_proj.bias": (kv * hd,),
                               m + "v_proj.weight": (d, kv * hd),
                               m + "v_proj.bias": (kv * hd,)})
        p = at + "mlp."
        shapes.update({p + "gate_proj.weight": (d, f),
                       p + "up_proj.weight": (d, f),
                       p + "down_proj.weight": (f, d)})
    return shapes


def compared_leaves(cfg):
    """The matrices (``common.matrix_leaves``) without the Mamba mixers'
    ``x_proj.weight``, for the reason ``matrix_leaves`` leaves the biases
    out: its gradient is ``x^T [d dt_r | dB | dC]`` with ``x`` behind a
    SiLU, whose rows share a large mean, so a column's norm is that mean
    times ONE sum over the positions of a signed ``dB_t[n]`` (or ``dC``,
    ``d dt_r``) that all but cancels: 32 + ``dt_rank`` such sums decide
    the leaf's norm, and a bfloat16 program reads rounding beside them.
    Seen on the chip at the cell's size (PR 50, twelve seeds): the two
    leaves read 0.1-2.0 % off (0.45 % the median seed, one seed 2.0 %, a
    ratio's heavy tail) where the worst of the other 41 reads 0.013-0.021 %
    on every seed and the float8 control 2.4-3.3 %: with them the worst
    leaf's limit would stand a hundred times looser. Their part of the
    model is still compared: through every other leaf's gradient (``dB``,
    ``dC`` and the step sizes reach ``in_proj`` and the taps) and the
    losses."""
    return [k for k in matrix_leaves(param_shapes(cfg))
            if not k.endswith("x_proj.weight")]


_WRITERS = ("out_proj.weight", "o_proj.weight", "down_proj.weight")


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call
    (configuration file, ``assumed.weights``): matrices normal(0,
    ``initializer_range``), the embedding included, the projections that
    write to the residual stream divided by ``sqrt(2 * num_hidden_layers)``
    with ``rescale_prenorm_residual``; taps and the convolution's bias
    uniform in ``+- 1 / sqrt(taps)``; ``A_log = log(1..N)`` in every
    channel, ``D = 1``; ``dt_proj``'s bias the inverse soft-plus of step
    sizes log-uniform in ``[time_step_min, time_step_max]``; the lambda
    vectors normal(0, ``lambda_std``); norm scales 1; every other bias
    normal(0, ``bias_std``), 0 as published."""
    shapes = param_shapes(cfg)
    std = cfg.get("initializer_range", 0.02)
    lam, bias_std = cfg.get("lambda_std", 0.1), cfg.get("bias_std", 0.0)
    writer = 1.0
    if cfg.get("rescale_prenorm_residual", False):
        writer = 1.0 / math.sqrt(2 * cfg["num_hidden_layers"])
    _, n, taps, _ = mamba_sizes(cfg)
    tap = 1.0 / math.sqrt(taps)
    lo, hi = (math.log(cfg.get(k, v)) for k, v in (
        ("time_step_min", 0.001), ("time_step_max", 0.1)))

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            k = jax.random.fold_in(key, i)
            if name.endswith(("conv_weight", "conv_bias")):
                out[name] = jax.random.uniform(k, shape, jnp.float32,
                                               -tap, tap)
            elif name.endswith("A_log"):
                out[name] = jnp.broadcast_to(
                    jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), shape)
            elif name.endswith("dt_proj.bias"):
                dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                                lo, hi))
                out[name] = dt + jnp.log(-jnp.expm1(-dt))
            elif ".lambda_" in name:
                out[name] = lam * jax.random.normal(k, shape, jnp.float32)
            elif name.endswith(".bias"):
                out[name] = bias_std * jax.random.normal(k, shape,
                                                         jnp.float32) \
                    if "layernorm" not in name else jnp.zeros(shape,
                                                              jnp.float32)
            elif len(shape) == 1:           # norm scales, D
                out[name] = jnp.ones(shape, jnp.float32)
            else:
                scale = std * writer if name.endswith(_WRITERS) else std
                out[name] = scale * jax.random.normal(k, shape, jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def selective_scan(x, delta, a, b, c, d_skip, state_dtype=jnp.float32):
    """``y`` [T, inner] of one sequence: ``H_t = exp(Delta_t A) * H_{t-1} +
    (Delta_t x_t) B_t^T``, ``y_t = H_t C_t + D x_t``, position by position
    from ``H = 0``. ``state_dtype`` is what the state is rounded to after
    every position (float32: not at all)."""
    t = x.shape[0]

    def position(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[:, None] * a) * h + (dt_t * x_t)[:, None] * b_t
        h = h.astype(state_dtype).astype(jnp.float32)
        return h, h @ c_t + d_skip * x_t

    @jax.checkpoint
    def chunk(h, rows):
        return jax.lax.scan(position, h, rows)

    rows = (x, delta, b, c)
    h0 = jnp.zeros(a.shape, jnp.float32)
    if t > SCAN_CHUNK and t % SCAN_CHUNK == 0:
        rows = jax.tree.map(lambda r: r.reshape(
            t // SCAN_CHUNK, SCAN_CHUNK, *r.shape[1:]), rows)
        _, y = jax.lax.scan(chunk, h0, rows)
        return y.reshape(t, -1)
    return chunk(h0, rows)[1]


def _mamba(cfg, w, n, ein):
    """(the mixer's result [T, d], the scan's un-gated result [T, inner])."""
    inner, n_state, taps, r = mamba_sizes(cfg)
    t = n.shape[0]
    xz = ein("sd,de->se", n, w["in_proj.weight"])
    x, z = xz[:, :inner], xz[:, inner:]
    padded = jnp.pad(x, [(taps - 1, 0), (0, 0)])
    shifted = jnp.stack([padded[j:j + t] for j in range(taps)], -1)
    x = jax.nn.silu(ein("tck,ck->tc", shifted, w["conv_weight"])
                    + w["conv_bias"])
    dbc = ein("se,ef->sf", x, w["x_proj.weight"])
    delta = jax.nn.softplus(ein("sr,re->se", dbc[:, :r], w["dt_proj.weight"])
                            + w["dt_proj.bias"])
    y = selective_scan(x, delta, -jnp.exp(w["A_log"]), dbc[:, r:r + n_state],
                       dbc[:, r + n_state:], w["D"],
                       cfg.get("scan_state_dtype", jnp.float32))
    return ein("se,ed->sd", y * jax.nn.silu(z), w["out_proj.weight"]), y


def key_value(cfg, w, n, ein):
    """``(k [kv heads, T, D], v [kv heads / 2, T, 2 D])`` of the normed
    rows: each key head once, a pair's two value heads side by side."""
    t, hd, kv = n.shape[0], head_dim(cfg), cfg["num_key_value_heads"]
    k = ein("sd,de->se", n, w["k_proj.weight"]) + w["k_proj.bias"]
    v = ein("sd,de->se", n, w["v_proj.weight"]) + w["v_proj.bias"]
    return (jnp.moveaxis(k.reshape(t, kv, hd), 1, 0),
            jnp.moveaxis(v.reshape(t, kv // 2, 2 * hd), 1, 0))


def lambda_of(w, lambda_init):
    return (jnp.exp(jnp.sum(w["lambda_q1"] * w["lambda_k1"]))
            - jnp.exp(jnp.sum(w["lambda_q2"] * w["lambda_k2"]))
            + lambda_init)


def differential_attention(cfg, w, n, k, v, source, window, ein):
    """The layer's result [T, d] from its normed rows ``n`` and the keys
    and values ``k``, ``v`` (its own or another layer's)."""
    t, hd = n.shape[0], head_dim(cfg)
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    lambda_init = 0.8 - 0.6 * math.exp(-0.3 * source)
    lam = lambda_of(w, lambda_init)
    at = jnp.arange(t)
    q = ein("sd,de->se", n, w["q_proj.weight"]) + w["q_proj.bias"]
    q = jnp.moveaxis(q.reshape(t, heads // 2, 2, hd), 0, 2)  # [pairs,2,T,D]

    @jax.checkpoint     # one pair of heads at a time, a block of its rows
    def one_pair(args):
        q_pair, k_pair, v_pair = args

        def rows(blk):
            q_b, at_b = blk                             # [rows, 2, D]
            seen = at_b[:, None] >= at[None, :]
            if window is not None:
                seen &= at_b[:, None] - at[None, :] < window
            maps = [jax.nn.softmax(jnp.where(
                seen, ein("qd,kd->qk", q_b[:, i], k_pair[i]) / math.sqrt(hd),
                -jnp.inf), -1) for i in (0, 1)]
            o = ein("qk,kd->qd", maps[0], v_pair) \
                - lam * ein("qk,kd->qd", maps[1], v_pair)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                                  + cfg["layer_norm_eps"])
            return (1.0 - lambda_init) * o * w["subln.weight"]

        return _by_token_blocks(rows, (jnp.moveaxis(q_pair, 0, 1), at))

    serves = jnp.arange(heads // 2) // (heads // kv)    # pair -> kv pair
    ctx = jax.lax.map(one_pair, (
        q, k.reshape(kv // 2, 2, t, hd)[serves], v[serves]))
    return ein("se,ed->sd",
               jnp.moveaxis(ctx, 0, 1).reshape(t, heads * hd),
               w["o_proj.weight"]) + w["o_proj.bias"]


def _memory_unit(w, n, memory, ein):
    return ein("se,ed->sd",
               jax.nn.silu(ein("sd,de->se", n, w["in_proj.weight"])) * memory,
               w["out_proj.weight"])


def _hidden(cfg, p, ids, ein):
    """The residual stream after the last block, [T, hidden], of one
    sequence."""
    eps = cfg["layer_norm_eps"]
    x = p["embed_tokens.weight"][ids]
    handed = {}
    for i, (kind, source, gives) in enumerate(layer_kinds(cfg)):
        @jax.checkpoint
        def block(x, w, reads, kind=kind, source=source):
            n = _layer_norm(x, w["input_layernorm.weight"],
                            w["input_layernorm.bias"], eps)
            m, extra = _under(w, "mixer."), ()
            if kind == "mamba":
                out, memory = _mamba(cfg, m, n, ein)
                extra = (memory,)
            elif kind == "memory_unit":
                out = _memory_unit(m, n, reads[0], ein)
            else:
                extra = reads if kind == "cross_attention" \
                    else key_value(cfg, m, n, ein)
                out = differential_attention(
                    cfg, m, n, *extra, source,
                    cfg["sliding_window"] if kind == "window_attention"
                    else None, ein)
            h = x + out
            n2 = _layer_norm(h, w["post_attention_layernorm.weight"],
                             w["post_attention_layernorm.bias"], eps)
            ff = _under(w, "mlp.")
            return h + _by_token_blocks(
                lambda blk: _gated_mlp(blk, ff["gate_proj.weight"],
                                       ff["up_proj.weight"],
                                       ff["down_proj.weight"], ein), n2), extra
        x, extra = block(x, _under(p, f"layers.{i}."), handed.get(kind, ()))
        if gives:
            handed["memory_unit" if kind == "mamba"
                   else "cross_attention"] = extra
    return x


def _logits(cfg, p, h, ein):
    return ein("sd,vd->sv",
               _layer_norm(h, p["final_layernorm.weight"],
                           p["final_layernorm.bias"], cfg["layer_norm_eps"]),
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
    return what ``common.follow`` returns (``lfm2_moe.train``: parameters
    and moments donated to each step, the seed's weights made a second time
    for the change, so that the chip holds 16 bytes a parameter)."""
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
