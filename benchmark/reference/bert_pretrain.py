"""Plain reference for BERT pre-training: float32 ``jax.numpy``, no
kernels, nothing of paddle_tpu.

Model: Devlin et al., arXiv:1810.04805, section 3 and appendix A.2 — token
+ position + segment embeddings, layer norm; L post-norm encoder layers
(multi-head self-attention, then a GELU feed-forward, each followed by a
residual add and layer norm); a tanh pooler on the first token; the
masked-LM head (dense, GELU, layer norm, the tied embedding matrix plus a
bias) and the next-sentence head. GELU is the exact erf form. The loss is
the mean cross entropy over masked positions plus the mean next-sentence
cross entropy. Initialisation is the paper's: truncated normal with
standard deviation 0.02, zero biases, unit layer-norm scales.

Optimizer: AdamW (Loshchilov & Hutter, arXiv:1711.05101, algorithm 2 with
Kingma & Ba's bias-corrected moments), decay on every parameter.

Names and layouts are the served model's state-dict names, so that the
same seeded arrays can be loaded there: a linear weight is [in, out]; the
fused ``qkv`` weight's 3·H·Dh columns are ordered (q|k|v, head, Dh).

The batch is walked in blocks of rows with each block recomputed in the
backward pass, so that three steps at the timed size fit beside nothing
else in a few GB.
"""
import math

import jax
import jax.numpy as jnp

from .common import bilinear, follow, leaf_norms, matrix_leaves, seed_key

BLOCK_TOKENS = 2048     # rows per block = BLOCK_TOKENS // seq

# What `correct` holds each compared number against is a cell's own:
# benchmark/limits/<cell>.json, beside the readings it was set from.


def param_shapes(cfg):
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    shapes = {
        "bert.embeddings.word_embeddings.weight": (v, d),
        "bert.embeddings.position_embeddings.weight":
            (cfg["max_position_embeddings"], d),
        "bert.embeddings.token_type_embeddings.weight":
            (cfg["type_vocab_size"], d),
        "bert.embeddings.norm.weight": (d,),
        "bert.embeddings.norm.bias": (d,),
        "bert.pooler.weight": (d, d), "bert.pooler.bias": (d,),
        "mlm_transform.weight": (d, d), "mlm_transform.bias": (d,),
        "mlm_norm.weight": (d,), "mlm_norm.bias": (d,),
        "mlm_bias": (v,),
        "nsp.weight": (d, 2), "nsp.bias": (2,),
    }
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert.encoder.{i}."
        shapes.update({
            p + "attention.qkv.weight": (d, 3 * d),
            p + "attention.qkv.bias": (3 * d,),
            p + "attention.out.weight": (d, d),
            p + "attention.out.bias": (d,),
            p + "attn_norm.weight": (d,), p + "attn_norm.bias": (d,),
            p + "ffn1.weight": (d, f), p + "ffn1.bias": (f,),
            p + "ffn2.weight": (f, d), p + "ffn2.bias": (d,),
            p + "ffn_norm.weight": (d,), p + "ffn_norm.bias": (d,),
        })
    return shapes


def compared_leaves(cfg):
    return matrix_leaves(param_shapes(cfg))


def init_weights(cfg, seed):
    """Every parameter from the seed, in float32, in one jitted call."""
    shapes = param_shapes(cfg)
    std = cfg["initializer_range"]

    def make(key):
        out = {}
        for i, (name, shape) in enumerate(sorted(shapes.items())):
            if name.endswith("norm.weight"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif len(shape) == 1:
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                out[name] = std * jax.random.truncated_normal(
                    jax.random.fold_in(key, i), -2.0, 2.0, shape, jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed))


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _xent(logits, labels):
    return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, labels[..., None], -1)[..., 0]


def forward(cfg, p, ids, types, precision="float32"):
    """(masked-LM logits [B, S, V], next-sentence logits [B, 2])."""
    def ein(spec, a, b):
        return bilinear(lambda x, y: jnp.einsum(spec, x, y), precision)(a, b)

    eps, heads = cfg["layer_norm_eps"], cfg["num_attention_heads"]
    b, s = ids.shape
    d = cfg["hidden_size"]
    dh = d // heads
    emb = "bert.embeddings."
    x = (p[emb + "word_embeddings.weight"][ids]
         + p[emb + "position_embeddings.weight"][:s][None]
         + p[emb + "token_type_embeddings.weight"][types])
    x = _layer_norm(x, p[emb + "norm.weight"], p[emb + "norm.bias"], eps)
    for i in range(cfg["num_hidden_layers"]):
        n = f"bert.encoder.{i}."
        qkv = ein("bsd,de->bse", x, p[n + "attention.qkv.weight"]) \
            + p[n + "attention.qkv.bias"]
        q, k, v = jnp.moveaxis(qkv.reshape(b, s, 3, heads, dh), 2, 0)
        scores = ein("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        ctx = ein("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        att = ein("bsd,de->bse", ctx.reshape(b, s, d),
                  p[n + "attention.out.weight"]) + p[n + "attention.out.bias"]
        x = _layer_norm(x + att, p[n + "attn_norm.weight"],
                        p[n + "attn_norm.bias"], eps)
        h = _gelu(ein("bsd,df->bsf", x, p[n + "ffn1.weight"])
                  + p[n + "ffn1.bias"])
        h = ein("bsf,fd->bsd", h, p[n + "ffn2.weight"]) + p[n + "ffn2.bias"]
        x = _layer_norm(x + h, p[n + "ffn_norm.weight"],
                        p[n + "ffn_norm.bias"], eps)
    pooled = jnp.tanh(ein("bd,de->be", x[:, 0], p["bert.pooler.weight"])
                      + p["bert.pooler.bias"])
    h = _gelu(ein("bsd,de->bse", x, p["mlm_transform.weight"])
              + p["mlm_transform.bias"])
    h = _layer_norm(h, p["mlm_norm.weight"], p["mlm_norm.bias"], eps)
    logits = ein("bsd,vd->bsv", h, p[emb + "word_embeddings.weight"]) \
        + p["mlm_bias"]
    nsp = ein("bd,dc->bc", pooled, p["nsp.weight"]) + p["nsp.bias"]
    return logits, nsp


def loss_fn(cfg, p, batch, precision="float32"):
    """The step's loss over the whole batch, one block of rows at a time."""
    ids, types, mlm, nsp = batch
    rows, seq = ids.shape
    r = max(1, min(rows, BLOCK_TOKENS // seq))
    while rows % r:
        r -= 1

    @jax.checkpoint
    def block_sums(p, blk):
        b_ids, b_types, b_mlm, b_nsp = blk
        logits, nsp_logits = forward(cfg, p, b_ids, b_types, precision)
        keep = b_mlm >= 0
        mlm_ce = _xent(logits, jnp.where(keep, b_mlm, 0))
        return jnp.stack([jnp.sum(jnp.where(keep, mlm_ce, 0.0)),
                          jnp.sum(_xent(nsp_logits, b_nsp))])

    blocks = (ids.reshape(-1, r, seq), types.reshape(-1, r, seq),
              mlm.reshape(-1, r, seq), nsp.reshape(-1, r))
    sums, _ = jax.lax.scan(lambda c, blk: (c + block_sums(p, blk), None),
                           jnp.zeros(2, jnp.float32), blocks)
    n_masked = jnp.maximum(jnp.sum(mlm >= 0), 1)
    return sums[0] / n_masked + sums[1] / rows


def adamw_update(hyper, p, g, m, v, t):
    b1, b2, eps = hyper["beta1"], hyper["beta2"], hyper["epsilon"]
    lr, wd = hyper["learning_rate"], hyper["weight_decay"]
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mhat = m / (1 - b1 ** t)
    vhat = v / (1 - b2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + eps) - lr * wd * p, m, v


def train(cfg, hyper, seed, batches, precision="float32"):
    """Follow ``len(batches)`` optimizer steps from the seed's weights; see
    ``common.follow`` for what comes back."""
    def step(p, slots, t, batch):
        m, v = slots
        loss, g = jax.value_and_grad(
            lambda q: loss_fn(cfg, q, batch, precision))(p)
        new = {k: adamw_update(hyper, p[k], g[k], m[k], v[k], t) for k in p}
        return ({k: n[0] for k, n in new.items()},
                ({k: n[1] for k, n in new.items()},
                 {k: n[2] for k, n in new.items()}), loss, leaf_norms(g))

    with jax.default_matmul_precision("highest"):
        return follow(init_weights(cfg, seed), 2, step, batches)
