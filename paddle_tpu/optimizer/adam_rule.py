"""THE Adam / AdamW update rule (reference: adam_op.cc, adamw in later
paddle), over one array of any shape: a parameter leaf, a shard of one, or
a flat arena buffer. ``optimizer.Adam``, ``optimizer.AdamW`` (per leaf and
flat arena), the static Executor's arena path and the megatron trainer all
call this one function.

Plain XLA on purpose. Under ``jit.to_static`` XLA puts the update into the
epilogue of the weight-gradient matmul, so the gradient never reaches HBM;
a Pallas kernel cannot be an epilogue and would make XLA write every
gradient for the kernel to read back (PERF.md section 6, PR 29).
"""
import jax.numpy as jnp


def adam_rule(p, g, m, v, lr, beta1_pow, beta2_pow, *, beta1=0.9,
              beta2=0.999, eps=1e-8, weight_decay=0.0, mask=None):
    """Returns (new_p, new_m, new_v). ``beta1_pow`` / ``beta2_pow`` are
    the bias-correction powers of THIS step (already multiplied in).
    ``weight_decay`` is AdamW's decoupled decay. ``mask`` (bool, p's
    shape) freezes the elements of arena members that produced no
    gradient this step.

    The cast order is part of the rule: ``astype(p.dtype)`` after the Adam
    term and again after the decay. The float32 lr would otherwise promote
    a bfloat16 parameter (dtype drift = a state-shape recompile), and the
    flat arena is held bit-identical per element to the per-leaf update."""
    new_m = beta1 * m + (1 - beta1) * g
    new_v = beta2 * v + (1 - beta2) * g * g
    mhat = new_m / (1 - beta1_pow)
    vhat = new_v / (1 - beta2_pow)
    new_p = (p - lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype)
    if weight_decay:
        new_p = (new_p - lr * weight_decay * p).astype(p.dtype)
    if mask is not None:
        new_p = jnp.where(mask, new_p, p)
        new_m = jnp.where(mask, new_m, m)
        new_v = jnp.where(mask, new_v, v)
    return new_p, new_m, new_v
