"""paddle_tpu.parallel.collective — collective communication.

TPU-native rebuild of the reference's collective operators
(reference: paddle/fluid/operators/collective/{c_allreduce_op, c_allgather_op,
c_reducescatter_op, c_broadcast_op, barrier_op, c_gen_nccl_id_op}.* and
python/paddle/fluid/layers/collective.py, transpiler/collective.py).

NCCL rings become XLA collectives on the ICI mesh: inside a
``shard_map``/``pjit`` region the ops lower to `lax.psum` / `all_gather` /
`psum_scatter` / `ppermute`, which XLA schedules directly onto ICI links —
there is no NCCL-style id bootstrap (gen_nccl_id) because device topology is
part of the mesh. Outside an SPMD region (single chip eager) they are
identity/no-ops, matching single-process semantics of the reference.
"""
from __future__ import annotations

import contextlib

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P, NamedSharding

from ..tensor import Tensor, as_tensor
from ..dispatch import apply
from .. import monitor as _monitor

# ---------------------------------------------------------------------------
# global mesh registry (the TPU analogue of the reference's communicator /
# ParallelContext state)

_global_mesh = None


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh
    return mesh


def get_mesh() -> Mesh:
    return _global_mesh


def make_mesh(axes: dict, devices=None) -> Mesh:
    """Create and register a Mesh, e.g. make_mesh({'dp': 2, 'tp': 4})."""
    devices = devices if devices is not None else jax.devices()
    names = tuple(axes)
    sizes = tuple(axes[n] for n in names)
    need = int(np.prod(sizes))
    if need > len(devices):
        raise ValueError(f"mesh needs {need} devices, have {len(devices)}")
    arr = np.asarray(devices[:need]).reshape(sizes)
    return set_mesh(Mesh(arr, names))


def replicated(x, mesh=None):
    """Place an array/Tensor replicated over the mesh."""
    mesh = mesh or _global_mesh
    if mesh is None:
        return x
    sh = NamedSharding(mesh, P())
    if isinstance(x, Tensor):
        x.data = jax.device_put(x.data, sh)
        return x
    return jax.device_put(x, sh)


def shard(x, spec, mesh=None):
    """Place an array/Tensor with a PartitionSpec over the mesh."""
    mesh = mesh or _global_mesh
    if mesh is None:
        return x
    sh = NamedSharding(mesh, spec if isinstance(spec, P) else P(*spec))
    if isinstance(x, Tensor):
        x.data = jax.device_put(x.data, sh)
        return x
    return jax.device_put(x, sh)


# ---------------------------------------------------------------------------
# SPMD-region detection: collectives need an axis name bound by
# shard_map/pmap; in plain eager (or plain jit) they act as identity.

axis_size = lax.axis_size   # static inside an SPMD region, NameError out


def in_spmd_context(axis_name=None):
    try:
        if axis_name is not None:
            axis_size(axis_name)
            return True
        return False
    except (NameError, KeyError, Exception):
        return False


# ---------------------------------------------------------------------------
# collectives (reference: c_allreduce_{sum,max,min,prod}, c_allgather,
# c_reducescatter, c_broadcast, barrier)

def _maybe(axis_name):
    return axis_name is not None and in_spmd_context(axis_name)


def _account(op, x, axis_name):
    """Monitor accounting for one issued collective: op count + payload
    bytes by mesh axis, plus a ``collective.<op>`` instant marker on the
    monitor.trace timeline (so collective issue sites line up against
    the executor/step spans in the Perfetto export). Runs AFTER the
    SPMD gate, so eager identity fallbacks don't count. Shapes are
    static under shard_map tracing, so this works on tracers; bytes are
    the per-shard payload, and inside a jitted region the record is per
    trace, not per device execution."""
    if not _monitor.enabled():
        return
    a = x.data if isinstance(x, Tensor) else x
    shape = tuple(getattr(a, "shape", ()) or ())
    try:
        itemsize = jnp.dtype(getattr(a, "dtype", jnp.float32)).itemsize
    except TypeError:
        itemsize = 4
    nbytes = int(np.prod(shape, dtype=np.int64)) * itemsize if shape \
        else itemsize
    _monitor.record_collective(op, axis_name, nbytes)


def all_reduce(x, op="sum", axis_name="dp", group=None):
    """c_allreduce_* → lax.psum/pmean/pmax/pmin on the ICI mesh axis.
    ``op="mean"`` is first-class (lax.pmean) — callers must not
    hand-divide a psum by the axis size."""
    if not _maybe(axis_name):
        return as_tensor(x)
    _account(f"c_allreduce_{op}", x, axis_name)
    fns = {"sum": lax.psum, "mean": lax.pmean, "max": lax.pmax,
           "min": lax.pmin,
           "prod": lambda v, n: jnp.exp(lax.psum(jnp.log(v), n))}
    if op not in fns:
        raise ValueError(
            f"all_reduce op {op!r} unknown; supported: {sorted(fns)}")
    fn = fns[op]
    return apply(lambda x: fn(x, axis_name), (x,), name=f"c_allreduce_{op}")


def all_gather(x, axis=0, axis_name="dp", group=None):
    """c_allgather → lax.all_gather along the mesh axis."""
    if not _maybe(axis_name):
        return as_tensor(x)
    _account("c_allgather", x, axis_name)
    return apply(lambda x: lax.all_gather(x, axis_name, axis=axis,
                                          tiled=True),
                 (x,), name="c_allgather")


def reduce_scatter(x, axis=0, axis_name="dp", group=None):
    """c_reducescatter → lax.psum_scatter."""
    if not _maybe(axis_name):
        return as_tensor(x)
    _account("c_reducescatter", x, axis_name)
    return apply(lambda x: lax.psum_scatter(x, axis_name,
                                            scatter_dimension=axis,
                                            tiled=True),
                 (x,), name="c_reducescatter")


def broadcast(x, src=0, axis_name="dp", group=None):
    """c_broadcast: every rank takes rank-src's value (select+psum)."""
    if not _maybe(axis_name):
        return as_tensor(x)
    _account("c_broadcast", x, axis_name)

    def impl(x):
        idx = lax.axis_index(axis_name)
        masked = jnp.where(idx == src, x, jnp.zeros_like(x))
        return lax.psum(masked, axis_name)

    return apply(impl, (x,), name="c_broadcast")


def all_to_all(x, split_axis=0, concat_axis=0, axis_name="dp", group=None):
    """alltoall_op → lax.all_to_all (the sequence/expert-parallel workhorse)."""
    if not _maybe(axis_name):
        return as_tensor(x)
    _account("alltoall", x, axis_name)
    return apply(lambda x: lax.all_to_all(x, axis_name, split_axis,
                                          concat_axis, tiled=True),
                 (x,), name="alltoall")


def ppermute(x, perm, axis_name="dp"):
    """Point-to-point ring permute (building block for ring attention and
    pipeline parallelism)."""
    if not _maybe(axis_name):
        return as_tensor(x)
    _account("ppermute", x, axis_name)
    return apply(lambda x: lax.ppermute(x, axis_name, perm), (x,),
                 name="ppermute")


def barrier(axis_name="dp", group=None):
    """barrier_op — on XLA a barrier is an all-reduce of a scalar."""
    if not _maybe(axis_name):
        return
    _account("barrier", jnp.zeros((), jnp.float32), axis_name)
    lax.psum(jnp.zeros((), jnp.float32), axis_name)


def rank(axis_name="dp"):
    if not _maybe(axis_name):
        return 0
    return lax.axis_index(axis_name)


def world_size(axis_name="dp"):
    if not _maybe(axis_name):
        return 1
    return axis_size(axis_name)


# reference-parity aliases (fluid.layers.collective underscored names)
_c_allreduce = all_reduce
_c_allgather = all_gather
_c_reducescatter = reduce_scatter
_c_broadcast = broadcast


def matmul_reduce_scatter(x, w, axis_name="tp", fused=True):
    """Fused matmul-then-reduce-scatter for the tensor-parallel exit of
    a row-split layer (fused computation-collectives, arxiv 2305.06942;
    reference analogue: the c_reducescatter op a Megatron row layer
    would issue after its partial matmul).

    ``x @ w`` where x is [m, k_local] and w is [k_local, N] with N
    divisible by the axis size; every rank holds a partial [m, N]
    product that must be reduce-scattered over the last dim. The
    unfused form is ``lax.psum_scatter(x @ w, ...)`` — the full partial
    product materialises, then the wire moves it. The fused schedule
    interleaves per-block matmuls with ring ppermute hops of the
    accumulator (start at column block (r-1)%n, permute forward, add
    block (r-t-2)%n each hop), so the collective for block t rides
    under the matmul for block t+1 and rank r ends holding fully
    reduced block r — bit-compatible layout with
    ``lax.psum_scatter(..., tiled=True)``. Outside an SPMD region it
    degrades to the plain local matmul (reduce_scatter's identity
    semantics)."""
    if not _maybe(axis_name):
        a = x.data if isinstance(x, Tensor) else x
        b = w.data if isinstance(w, Tensor) else w
        return as_tensor(jnp.asarray(a) @ jnp.asarray(b))
    _account("matmul_reduce_scatter", w, axis_name)

    def impl(x, w):
        n = axis_size(axis_name)
        m, N = x.shape[0], w.shape[1]
        if N % n:
            raise ValueError(
                f"matmul_reduce_scatter: output dim {N} not divisible "
                f"by axis {axis_name!r} size {n}")
        bs = N // n
        if not fused:
            return lax.psum_scatter(x @ w, axis_name,
                                    scatter_dimension=1, tiled=True)
        r = lax.axis_index(axis_name)
        fwd = [(i, (i + 1) % n) for i in range(n)]

        def block(j):
            return x @ lax.dynamic_slice(w, (0, j * bs),
                                         (w.shape[0], bs))

        acc = block((r - 1) % n)
        for t in range(n - 1):
            acc = lax.ppermute(acc, axis_name, fwd)
            acc = acc + block((r - t - 2) % n)
        return acc

    return apply(impl, (x, w), name="matmul_reduce_scatter")


QUANTIZED_WIRE_BITS = (4, 8)


def all_reduce_quantized(x, axis_name="dp", bits=8, op="sum"):
    """Quantized ring all-reduce: int8 (or packed-int4) chunks + one
    f32 scale per hop on the wire instead of f32 tensors (the EQuARX
    direction, arxiv 2506.17615; the reference's analogous bandwidth
    lever is DGC sparsification over NCCL). Ring reduce-scatter then
    ring all-gather, n-1 ppermute hops each, with per-hop symmetric
    requantization — wire bytes drop ~4x (int8) / ~8x (int4, two
    values packed per byte) for bf16/f32 grads at a bounded
    quantization error that grows with ring length (callers should
    reserve it for bandwidth-bound DCN/large-dp regimes; exact psum
    stays the default everywhere).

    Only meaningful inside shard_map with `axis_name`; returns the SUM
    over the axis (like lax.psum), or the mean with ``op="mean"`` —
    the division happens once, after the ring, so both ops share one
    wire schedule."""
    if bits not in QUANTIZED_WIRE_BITS:
        raise ValueError(
            f"quantized wire width bits={bits} unsupported; supported "
            f"widths: {QUANTIZED_WIRE_BITS} (int8, packed int4)")
    if op not in ("sum", "mean"):
        raise ValueError(
            f"all_reduce_quantized op {op!r} unknown; supported: "
            f"['mean', 'sum']")
    n = axis_size(axis_name)
    if n == 1:
        return x
    qmax = 127.0 if bits == 8 else 7.0

    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    c = -(-flat.shape[0] // n)
    if bits == 4:
        c += c % 2  # packed pairs: chunk length must be even
    flat = jnp.pad(flat, (0, n * c - flat.shape[0]))
    chunks = flat.reshape(n, c)
    r = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    if bits == 8:
        def quant(v):
            s = jnp.max(jnp.abs(v)) / qmax + 1e-30
            q = jnp.clip(jnp.round(v / s), -127, 127).astype(jnp.int8)
            return q, s

        def dequant(q, s):
            return q.astype(jnp.float32) * s
    else:
        # packed int4: q ∈ [-7, 7] biased to [1, 15], two nibbles per
        # uint8 byte — ~8x less wire than f32 plus one scale per hop
        def quant(v):
            s = jnp.max(jnp.abs(v)) / qmax + 1e-30
            q = jnp.clip(jnp.round(v / s), -7, 7)
            b = (q + 8.0).astype(jnp.uint8)
            packed = b[..., 0::2] | (b[..., 1::2] << 4)
            return packed, s

        def dequant(packed, s):
            lo = (packed & 0xF).astype(jnp.float32) - 8.0
            hi = (packed >> 4).astype(jnp.float32) - 8.0
            q = jnp.stack([lo, hi], axis=-1).reshape(
                packed.shape[:-1] + (2 * packed.shape[-1],))
            return q * s

    # ring reduce-scatter: after n-1 hops rank r owns the fully
    # reduced chunk (r + 1) % n
    for t in range(n - 1):
        send_idx = (r - t) % n
        recv_idx = (r - t - 1) % n
        piece = lax.dynamic_slice(chunks, (send_idx, 0), (1, c))
        q, s = quant(piece)
        q = lax.ppermute(q, axis_name, fwd)
        s = lax.ppermute(s, axis_name, fwd)
        got = dequant(q, s)
        cur = lax.dynamic_slice(chunks, (recv_idx, 0), (1, c))
        chunks = lax.dynamic_update_slice(chunks, cur + got,
                                          (recv_idx, 0))

    # ring all-gather of the owned (reduced) chunks. Each chunk is
    # quantized ONCE at its owner and the same (q, scale) pair rides
    # the whole ring — so every rank reconstructs bit-identical values
    # (per-hop requantization here would give each rank a different
    # approximation, and replicated params would silently drift).
    own_idx = (r + 1) % n
    own = lax.dynamic_slice(chunks, (own_idx, 0), (1, c))
    q, s = quant(own)
    # store the dequantized form locally too — identical on all ranks
    chunks = lax.dynamic_update_slice(chunks, dequant(q, s),
                                      (own_idx, 0))
    for t in range(n - 1):
        q = lax.ppermute(q, axis_name, fwd)
        s = lax.ppermute(s, axis_name, fwd)
        idx = (r - t) % n  # arriving chunk originated at rank
        # (r - t - 1), which owns chunk (r - t) % n
        chunks = lax.dynamic_update_slice(chunks, dequant(q, s),
                                          (idx, 0))

    out = chunks.reshape(-1)[:int(np.prod(shape))].reshape(shape)
    if op == "mean":
        # one division AFTER the ring: every rank scales the identical
        # dequantized sum, so the cross-rank bit-equality invariant of
        # the all-gather phase survives
        out = out / n
    return out.astype(x.dtype)
