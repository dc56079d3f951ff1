"""What every plain reference shares: the seed's key, the bilinear
operator in the precision asked for, and the norms the comparison reads.

Imports nothing of paddle_tpu. ``float32`` is the reference proper (run
it under ``jax.default_matmul_precision("highest")``). ``float8`` is the
control of the contract's step 2 for a configuration that states
bfloat16 compute. As bfloat16 compute rounds a product's operands and
its result to bfloat16, so here every matrix product and convolution,
forward and backward, takes its two operands and gives its result rounded
to an 8-bit float with one scale per tensor (e4m3 for values, e5m2 for
gradients: the formats of Micikevicius et al., arXiv:2209.05433), and
accumulates in float32. Rounding the operands alone adds zero-mean noise
that a norm averages away (seen on the chip, PR 24: some seeds' norms then
sit as close to float32's as the bf16 program's do).
"""
import jax
import jax.numpy as jnp

PRECISIONS = ("float32", "float8")


def seed_key(seed):
    """A key from any whole number up to 2**63, with no 32-bit wrap."""
    seed = int(seed)
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    return jax.random.fold_in(jax.random.key(lo), hi)


# (mantissa bits, smallest normal exponent, largest finite value)
_E4M3 = (3, -6, 448.0)
_E5M2 = (2, -14, 57344.0)


def _round8(x, fmt):
    """``x`` rounded to the 8-bit float ``fmt`` with one scale for the
    tensor: its largest magnitude lands on the format's largest value.
    Worked in float32 arithmetic (an exact exponent from ``frexp``, then
    round-to-nearest on that binade's grid, the subnormal grid below the
    smallest normal), so that it is the same on every backend: the chip's
    own conversion to ``float8_e4m3fn`` gave non-finite values (PR 24)."""
    bits, min_exp, peak = fmt
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / peak
    y = x / scale
    _, e = jnp.frexp(y)                    # |y| in [2**(e-1), 2**e)
    step = jnp.ldexp(jnp.float32(1.0), jnp.maximum(e - 1, min_exp) - bits)
    return jnp.round(y / step) * step * scale


def bilinear(op, precision):
    """``op(a, b)`` in ``precision``: itself for float32; for float8 the
    same product with operands and result rounded, and in the backward
    pass the cotangent and both transposed products' results rounded."""
    if precision == "float32":
        return op
    if precision != "float8":
        raise ValueError(f"precision {precision!r} is not one of "
                         f"{PRECISIONS}")

    values, grads = _E4M3, _E5M2

    def fwd(a, b):
        qa, qb = _round8(a, values), _round8(b, values)
        return _round8(op(qa, qb), values), (qa, qb)

    @jax.custom_vjp
    def q_op(a, b):
        return fwd(a, b)[0]

    def bwd(res, ct):
        _, vjp = jax.vjp(op, *res)
        return tuple(_round8(g, grads) for g in vjp(_round8(ct, grads)))

    q_op.defvjp(fwd, bwd)
    return q_op


def matrix_leaves(shapes):
    """The parameters whose gradient and change `correct` compares norm by
    norm: the matrices and convolutions (two dimensions or more). Biases
    and the scales and shifts of layer and batch norms are left out: their
    gradients are sums over every row or position that all but cancel, so
    what a run reads there is rounding noise as much as signal. Seen on the
    chip (PR 24): the key third of BERT's fused qkv bias has a gradient of
    exactly zero, which Adam scales up to a full step (its change read
    0.1-0.27 off); the 2-element next-sentence bias read 0.005-0.015 off; a
    batch norm's scale and shift, whose gradient has passed back through
    the next batch norm, read 0.2-0.46 off in bf16 and in float8 alike."""
    return sorted(k for k, shape in shapes.items() if len(shape) >= 2)


def leaf_norms(tree):
    """{name: l2 norm in float32} of a flat dict of arrays."""
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def diff_norms(new, old):
    return {k: jnp.sqrt(jnp.sum(jnp.square(
        new[k].astype(jnp.float32) - old[k].astype(jnp.float32))))
        for k in old}


def follow(params, n_slots, step, batches):
    """Drive ``step(params, slots, t, batch) -> (params, slots, loss, first
    gradient's leaf norms)`` through ``batches`` from ``params``, with
    ``n_slots`` optimizer slots that start at zero. Returns the host
    numbers `correct` compares: each step's loss, the norm of every leaf of
    the first gradient, the norm of every leaf's change after the last
    step."""
    start = params
    jstep = jax.jit(step, donate_argnums=(1,))
    slots = tuple(jax.tree.map(jnp.zeros_like, params)
                  for _ in range(n_slots))
    losses, first = [], None
    for t, batch in enumerate(batches, 1):
        params, slots, loss, norms = jstep(
            params, slots, jnp.float32(t),
            tuple(jnp.asarray(a) for a in batch))
        losses.append(float(loss))
        if first is None:
            first = jax.device_get(norms)
    delta = jax.device_get(jax.jit(diff_norms)(params, start))
    return {"loss": losses,
            "first_grad_norm": {k: float(x) for k, x in first.items()},
            "delta_norm": {k: float(x) for k, x in delta.items()}}
