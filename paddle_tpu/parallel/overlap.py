"""paddle_tpu.parallel.overlap — bucketed, overlapped, quantized grad sync.

The data-parallel gradient exchange as a *scheduled* communication plan
instead of one monolithic all-reduce at the end of backward (reference
analogue: the NCCL fused-allreduce + DGC bandwidth levers in
python/paddle/fluid/dygraph/parallel.py; direction per EQuARX,
arxiv 2506.17615, and fused computation-collectives, arxiv 2305.06942):

* :func:`plan_buckets` — order-preserving, size-bounded bucketing of a
  flat grad pytree (the same pad-to-a-small-bucket-set discipline as
  ``io.bucketing``, so bucket executables are reused, not re-minted).
* :func:`sync_tree` — the *in-SPMD* bucketed reduce for shard_map
  trainers (megatron): every bucket is one flat f32 vector reduced with
  ``lax.pmean``/``psum`` or the quantized ring
  (``collective.all_reduce_quantized``, int8 or packed-int4 wire).
* :class:`GradSyncScheduler` — the *host-level* scheduler for explicit
  DDP loops over stacked per-rank grads (``[n_dp, ...]`` leaves from
  :func:`local_value_and_grad`). Bucket reduces are jitted shard_map
  executables; in ``overlap`` mode they run on a dedicated comm-worker
  thread (XLA executions release the GIL, so they genuinely overlap the
  main thread's backward compute — observed as a separate
  ``comm.bucket_reduce`` track in the Chrome trace, not inferred), and
  ``async_apply`` (lag-1, mirroring the Executor's ``async_fetch``)
  lets step N apply the synced grads of step N-1 so almost no wire time
  stays on the critical path.

Exposed wire time is *measured*: every second the caller spends blocked
on an unfinished reduce lands in ``scheduler.exposed_wait_s`` and the
``comm.exposed_wait_s`` histogram; ``comm.bytes_wire`` vs
``comm.bytes_logical`` records what quantization saved.
``scripts/comm_smoke.py`` gates on both.

Mode knob (one string everywhere — DataParallel, MegatronConfig,
Optimizer, hapi/static entry points):

* ``"exact"``      — discrete f32 reduce on the caller's thread (the
  baseline whose wire time is fully exposed).
* ``"quantized"``  — same schedule, int8/int4 ring wire (``bits=``).
* ``"overlap"``    — bucket reduces launched on the comm worker as soon
  as each bucket's grads exist; implies lag-1 ``async_apply`` unless
  explicitly disabled. Inside a single shard_map region (``sync_tree``)
  "overlap" means *bucketed* issue — XLA's scheduler interleaves the
  independent per-bucket collectives with remaining compute; host-side
  lag-1 does not apply there.

Checkpoint discipline: ``state_dict()`` serialises the lag-1 pending
synced grads (materialised, NOT flushed), so a restore resumes
bit-identically with an uninterrupted run — comm_smoke gates on this.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .collective import all_reduce_quantized, axis_size, get_mesh
from ..io.bucketing import next_bucket
from .. import monitor as _monitor
from ..monitor import trace as _trace

__all__ = [
    "MODES", "SUPPORTED_BITS", "plan_buckets", "wire_bytes", "sync_tree",
    "sync_arena_flat", "local_value_and_grad", "GradSyncScheduler",
]

MODES = ("exact", "quantized", "overlap")
SUPPORTED_BITS = (4, 8)

# default bucket: 4 MiB of f32 grads — small enough that several buckets
# exist for bench-scale models, large enough to amortise dispatch
DEFAULT_BUCKET_BYTES = 4 << 20


def _check_mode(mode):
    if mode not in MODES:
        raise ValueError(
            f"grad_sync mode {mode!r} unknown; supported: {MODES}")
    return mode


def plan_buckets(sizes, bucket_bytes=DEFAULT_BUCKET_BYTES, itemsize=4):
    """Greedy, order-preserving bucketing: ``sizes`` are per-leaf
    element counts; returns a list of index lists, each bucket's total
    payload ≤ ``bucket_bytes`` (a single oversized leaf gets its own
    bucket). Order is preserved so buckets fill in the order backward
    produces grads — the property overlap relies on."""
    cap = max(int(bucket_bytes) // int(itemsize), 1)
    buckets, cur, cur_n = [], [], 0
    for i, sz in enumerate(sizes):
        sz = int(sz)
        if cur and cur_n + sz > cap:
            buckets.append(cur)
            cur, cur_n = [], 0
        cur.append(i)
        cur_n += sz
    if cur:
        buckets.append(cur)
    return buckets


def wire_bytes(n_elems, mode, bits=8, n_ranks=2):
    """Bytes of the wire *representation* of an ``n_elems`` f32 bucket
    payload: f32 for exact/overlap, ``bits``-wide ints plus the per-hop
    f32 scales for quantized (2·(n−1) transmitted chunk scales per
    rank). Representation size, not total link traffic — the comparable
    figure ``comm.bytes_logical`` records is the same payload at f32."""
    n_elems = int(n_elems)
    if mode != "quantized":
        return 4 * n_elems
    payload = (n_elems * int(bits) + 7) // 8
    return payload + 4 * max(2 * (int(n_ranks) - 1), 1)


def _account(mode, bits, n_ranks, logical_elems, n_buckets,
             wire=None):
    if not _monitor.enabled():
        return
    logical = 4 * int(logical_elems)
    wb = wire_bytes(logical_elems, wire or mode, bits, n_ranks)
    _monitor.counter("comm.bytes_logical").inc(logical)
    _monitor.counter("comm.bytes_wire").inc(wb)
    _monitor.counter("comm.buckets").inc(int(n_buckets))
    _monitor.counter(f"comm.sync.{mode}").inc()


# ---------------------------------------------------------------------------
# in-SPMD bucketed reduce (megatron / any shard_map trainer)

def _reduce_flat(flat, axis_name, mode, bits, op):
    if mode == "quantized":
        return all_reduce_quantized(flat, axis_name, bits=bits, op=op)
    return (lax.pmean if op == "mean" else lax.psum)(flat, axis_name)


def sync_tree(tree, axis_name="dp", mode="exact", bits=8,
              bucket_bytes=DEFAULT_BUCKET_BYTES, op="mean",
              extra_mean_axes=()):
    """Bucketed gradient sync *inside* a shard_map region: flatten the
    pytree, concatenate leaves into size-bounded f32 buckets (padded to
    the ``io.bucketing`` power-of-two set so bucket shapes stay in a
    small family), reduce each bucket over ``axis_name`` (exact psum /
    pmean, or the quantized ring for ``mode="quantized"``), then mean
    over any ``extra_mean_axes`` (megatron's sp). ``mode="overlap"``
    here means bucketed issue — the per-bucket collectives are
    independent, so XLA is free to interleave them with remaining
    compute. Returns the tree with every leaf reduced, original dtypes
    restored."""
    _check_mode(mode)
    if mode == "quantized" and bits not in SUPPORTED_BITS:
        raise ValueError(
            f"quantized wire width {bits} unsupported; "
            f"supported: {SUPPORTED_BITS}")
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves]
    plan = plan_buckets(sizes, bucket_bytes)
    try:
        n_ranks = axis_size(axis_name)
    except Exception:
        n_ranks = 1
    _account(mode, bits, n_ranks, sum(sizes), len(plan))
    out = [None] * len(leaves)
    for idxs in plan:
        flat = jnp.concatenate(
            [leaves[i].reshape(-1).astype(jnp.float32) for i in idxs])
        size = flat.shape[0]
        padded = next_bucket(size)
        if padded > size:
            flat = jnp.pad(flat, (0, padded - size))
        red = _reduce_flat(flat, axis_name, mode, bits, op)
        for ax in extra_mean_axes:
            red = lax.pmean(red, ax)
        off = 0
        for i in idxs:
            out[i] = red[off:off + sizes[i]] \
                .reshape(leaves[i].shape).astype(leaves[i].dtype)
            off += sizes[i]
    return jax.tree_util.tree_unflatten(treedef, out)


def sync_arena_flat(flat, bounds, axis_name="dp", mode="exact", bits=8,
                    op="mean"):
    """Bucketed reduce over a flat-arena gradient buffer *inside* a
    shard_map region: ``bounds`` is the arena's contiguous-slice bucket
    plan (``ParamArena.bucket_bounds()[tag]``), so every bucket is a
    static slice of ``flat`` — the per-leaf gather ``sync_tree`` pays is
    replaced by pure offsets, and the reassembly is one ordered concat
    XLA fuses with the downstream flat optimizer update. Padding to the
    ``io.bucketing`` size family keeps the quantized ring's executable
    reuse."""
    _check_mode(mode)
    if mode == "quantized" and bits not in SUPPORTED_BITS:
        raise ValueError(
            f"quantized wire width {bits} unsupported; "
            f"supported: {SUPPORTED_BITS}")
    try:
        n_ranks = axis_size(axis_name)
    except Exception:
        n_ranks = 1
    total = int(flat.shape[0])
    _account(mode, bits, n_ranks, total, len(bounds))
    orig = flat.dtype
    pieces = []
    for start, stop in bounds:
        seg = flat[start:stop].astype(jnp.float32)
        size = stop - start
        padded = next_bucket(size)
        if padded > size:
            seg = jnp.pad(seg, (0, padded - size))
        red = _reduce_flat(seg, axis_name, mode, bits, op)
        pieces.append(red[:size].astype(orig))
    return jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]


# ---------------------------------------------------------------------------
# host-level scheduler over stacked per-rank grads

def local_value_and_grad(loss_fn, mesh=None, axis_name="dp"):
    """Per-rank loss/grads for explicit-DDP loops: returns a jitted
    ``f(params, batch) -> (loss [n], grads)`` where every grad leaf is
    stacked ``[n, *param_shape]`` — one UNREDUCED row per ``axis_name``
    rank (params replicated, batch sharded on its leading dim). Feed
    the grads to :meth:`GradSyncScheduler.reduce`. Without a mesh the
    eager fallback returns the same shapes with n=1."""
    mesh = mesh or get_mesh()

    def _local(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        return (jnp.asarray(loss, jnp.float32)[None],
                jax.tree_util.tree_map(lambda g: g[None], grads))

    if mesh is None:
        return _local
    sm = jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)),
        check_vma=False)
    return jax.jit(sm)


class GradSyncScheduler:
    """Bucketed gradient-sync scheduler (see module docstring).

    Two integration surfaces:

    * :meth:`reduce` — stacked per-rank grads (``[n_dp, ...]`` leaves)
      from an explicit-DDP loop; buckets are reduced by jitted
      shard_map executables, on the comm-worker thread in ``overlap``
      mode, with lag-1 ``async_apply`` returning the *previous* step's
      synced tree (``None`` on the warm-up step — skip the apply).
    * :meth:`process` — ``Optimizer.step`` hook over eager
      ``(param, grad)`` pairs. Under GSPMD those grads arrive already
      reduced, so here the knob contributes lag-1 apply pipelining and
      ``comm.*`` accounting; the wire-level effects live in
      :meth:`reduce` / :func:`sync_tree`. Inside a traced step
      (jit.to_static) lag staging would leak tracers, so it passes
      through unchanged.
    """

    def __init__(self, mode="overlap", mesh=None, axis_name="dp",
                 bits=8, bucket_bytes=DEFAULT_BUCKET_BYTES,
                 async_apply=None, op="mean", quantized=None, plan=None):
        _check_mode(mode)
        if plan is not None:
            # a parallel.planner.MeshPlan supplies the mesh and the
            # grad-sync axis, so the scheduler reduces over exactly the
            # axis the plan shards batches on
            mesh = mesh if mesh is not None else plan.mesh
            axis_name = plan.grad_axis()
        if bits not in SUPPORTED_BITS:
            raise ValueError(
                f"quantized wire width {bits} unsupported; "
                f"supported: {SUPPORTED_BITS}")
        self.mode = mode
        self.bits = int(bits)
        # the wire format is orthogonal to scheduling: "quantized" mode
        # implies it, and overlap mode can opt in (quantized=True) to
        # run int8/int4 ring reduces on the comm worker
        self.quantized = (mode == "quantized") if quantized is None \
            else bool(quantized)
        self.op = op
        self.bucket_bytes = int(bucket_bytes)
        self.axis_name = axis_name
        self._mesh = mesh
        self.async_apply = (mode == "overlap") if async_apply is None \
            else bool(async_apply)
        self.steps = 0
        self.exposed_wait_s = 0.0
        self.last_plan = None   # bucket plan of the newest reduce()
        self._pool = None
        self._fn_cache = {}      # bucket signature -> jitted reduce
        self._plan_cache = {}    # leaves signature -> bucket plan
        self._pending = None     # (treedef, launches, n_leaves)
        self._restored = None    # leaves restored from a checkpoint
        self._pending_pg = None  # lag-1 state for process()
        self._lock = threading.Lock()

    # -- infrastructure ----------------------------------------------------
    @property
    def compiled_buckets(self):
        """Distinct bucket-reduce executables minted so far (the
        comm_smoke zero-extra-recompiles gate reads this)."""
        return len(self._fn_cache)

    def _mesh_now(self):
        return self._mesh or get_mesh()

    def _worker(self):
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="comm-worker")
        return self._pool

    def _plan(self, leaves):
        key = tuple((tuple(l.shape), str(jnp.result_type(l)))
                    for l in leaves)
        plan = self._plan_cache.get(key)
        if plan is None:
            # per-rank payload: leaves are stacked [n, ...]
            sizes = [int(np.prod(l.shape[1:])) if l.ndim > 1 else 1
                     for l in leaves]
            plan = plan_buckets(sizes, self.bucket_bytes)
            self._plan_cache[key] = plan
        return plan

    def _bucket_fn(self, bucket_leaves, mesh):
        shapes = tuple(tuple(l.shape[1:]) for l in bucket_leaves)
        dtypes = tuple(str(jnp.result_type(l)) for l in bucket_leaves)
        n = int(mesh.shape[self.axis_name]) if mesh is not None and \
            self.axis_name in getattr(mesh, "shape", {}) else 1
        wire = "quantized" if self.quantized else "exact"
        key = (shapes, dtypes, n, wire, self.bits, self.op)
        fn = self._fn_cache.get(key)
        if fn is not None:
            return fn
        sizes = [max(int(np.prod(s)), 1) for s in shapes]
        total = sum(sizes)
        padded = next_bucket(total)
        bits, op, axis = self.bits, self.op, self.axis_name

        def _unpack(red):
            out, off = [], 0
            for s, sz, dt in zip(shapes, sizes, dtypes):
                out.append(red[off:off + sz].reshape(s).astype(dt))
                off += sz
            return tuple(out)

        if mesh is None or n == 1:
            # eager fallback: the stacking axis IS the reduce axis
            def host_fn(*stacked):
                rfn = jnp.mean if op == "mean" else jnp.sum
                flat = jnp.concatenate(
                    [rfn(x.astype(jnp.float32), axis=0).reshape(-1)
                     for x in stacked])
                return _unpack(jnp.pad(flat, (0, padded - total)))
            fn = jax.jit(host_fn)
        else:
            def device_fn(*locals_):
                flat = jnp.concatenate(
                    [x.reshape(-1).astype(jnp.float32) for x in locals_])
                flat = jnp.pad(flat, (0, padded - total))
                return _unpack(_reduce_flat(flat, axis, wire, bits, op))

            fn = jax.jit(jax.shard_map(
                device_fn, mesh=mesh,
                in_specs=P(self.axis_name),
                out_specs=P(),
                check_vma=False))
        self._fn_cache[key] = fn
        if _monitor.enabled():
            _monitor.counter("comm.bucket_compile").inc()
        return fn

    # -- stacked-grad path (explicit DDP) ----------------------------------
    def reduce(self, grads):
        """Sync a stacked-grad pytree. Returns the synced tree with the
        rank axis reduced away — or, with ``async_apply``, the
        *previous* call's synced tree (``None`` on the first call)."""
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if not leaves:
            return grads if not self.async_apply else None
        mesh = self._mesh_now()
        n = int(mesh.shape[self.axis_name]) if mesh is not None and \
            self.axis_name in getattr(mesh, "shape", {}) else 1
        plan = self._plan(leaves)
        self.last_plan = plan
        per_rank = sum(int(np.prod(l.shape[1:])) if l.ndim > 1 else 1
                       for l in leaves)
        _account(self.mode, self.bits, max(n, 2), per_rank, len(plan),
                 wire="quantized" if self.quantized else "exact")
        use_worker = self.mode == "overlap" or self.async_apply
        launches = []
        for b_id, idxs in enumerate(plan):
            bucket = [leaves[i] for i in idxs]
            fn = self._bucket_fn(bucket, mesh)
            nbytes = 4 * sum(int(np.prod(l.shape[1:])) if l.ndim > 1
                             else 1 for l in bucket)
            if use_worker:
                fut = self._worker().submit(
                    self._run_bucket, fn, bucket, b_id, nbytes)
                launches.append((idxs, fut))
            else:
                t0 = time.perf_counter()
                res = self._run_bucket(fn, bucket, b_id, nbytes)
                self._note_exposed(time.perf_counter() - t0)
                launches.append((idxs, res))
        self.steps += 1
        if not self.async_apply:
            return self._collect((treedef, launches, len(leaves)))
        prev, self._pending = self._pending, (treedef, launches,
                                              len(leaves))
        if self._restored is not None:
            # lag-1 state carried through a checkpoint: the restored
            # synced grads are this step's apply, bit-identical to the
            # uninterrupted run
            restored, self._restored = self._restored, None
            return jax.tree_util.tree_unflatten(treedef, restored)
        if prev is None:
            if _monitor.enabled():
                _monitor.counter("comm.lag_warmup").inc()
            return None
        return self._collect(prev)

    def reduce_arena(self, stacked, bounds):
        """Arena path for explicit-DDP loops: ``stacked`` is ONE
        ``[n_dp, total]`` flat gradient buffer in arena layout;
        ``bounds`` its contiguous-slice bucket plan. Each bucket is a
        cheap contiguous slice (no per-leaf gather) fed through the
        standard launch/overlap/lag-1 machinery; returns the synced flat
        buffer (or None on the lag-1 warm-up step)."""
        segs = [stacked[:, a:b] for a, b in bounds]
        out = self.reduce(segs)
        if out is None:
            return None
        return jnp.concatenate(out) if len(out) > 1 else out[0]

    def _run_bucket(self, fn, bucket, b_id, nbytes):
        with _trace.span("comm.bucket_reduce", bucket=b_id,
                         bytes=nbytes, mode=self.mode):
            out = fn(*bucket)
            jax.block_until_ready(out)
        if _monitor.enabled():
            _monitor.counter("comm.reduce_launch").inc()
        return out

    def _collect(self, pending, count_exposed=True):
        treedef, launches, n_leaves = pending
        out = [None] * n_leaves
        t0 = time.perf_counter()
        with _trace.span("comm.wait", mode=self.mode):
            for idxs, item in launches:
                res = item.result() if isinstance(item, Future) else item
                for k, i in enumerate(idxs):
                    out[i] = res[k]
        if count_exposed:
            self._note_exposed(time.perf_counter() - t0)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _note_exposed(self, dt):
        self.exposed_wait_s += dt
        if _monitor.enabled():
            _monitor.histogram("comm.exposed_wait_s").observe(dt)
            _monitor.counter("comm.exposed_wait_s_total").inc(dt)

    def flush(self):
        """Drain the lag-1 tail: the final enqueued step's synced tree,
        or None when nothing is pending. Call once after the last
        training step so its gradient is not dropped."""
        if self._pending is None:
            return None
        pending, self._pending = self._pending, None
        return self._collect(pending)

    # -- Optimizer.step path (eager (param, grad) pairs) -------------------
    def process(self, params_grads):
        """Optimizer hook: lag-1 pipelining + accounting over eager
        pairs (grads already reduced under GSPMD — see class
        docstring). Returns pairs to apply now, or None on the lag-1
        warm-up step."""
        elems = sum(int(np.prod(np.shape(g))) for _, g in params_grads
                    if g is not None)
        _account(self.mode, self.bits, 2, elems, 1)
        traced = any(isinstance(g, jax.core.Tracer)
                     for _, g in params_grads if g is not None)
        if traced or not self.async_apply:
            return params_grads
        prev, self._pending_pg = self._pending_pg, list(params_grads)
        if self._restored is not None:
            restored, self._restored = self._restored, None
            params = [p for p, _ in params_grads]
            if len(restored) == len(params):
                return list(zip(params, [jnp.asarray(g)
                                         for g in restored]))
        if prev is None:
            if _monitor.enabled():
                _monitor.counter("comm.lag_warmup").inc()
            return None
        return prev

    def flush_process(self):
        """Drain the process()-path lag-1 tail."""
        prev, self._pending_pg = self._pending_pg, None
        return prev

    # -- checkpoint discipline ---------------------------------------------
    def state_dict(self):
        """Serialisable scheduler state. The lag-1 pending synced grads
        are MATERIALISED (waited for), never flushed — flushing would
        apply them early and diverge from the uninterrupted run."""
        sd = {"mode": self.mode, "steps": int(self.steps)}
        if self._pending is not None:
            synced = self._collect(self._pending, count_exposed=False)
            leaves, _ = jax.tree_util.tree_flatten(synced)
            sd["pending"] = [np.asarray(jax.device_get(x))
                             for x in leaves]
            # keep serving the same synced tree to the next reduce()
            # call — state_dict() must not consume the pipeline
            self._pending = None
            self._restored = [jnp.asarray(x) for x in sd["pending"]]
        elif self._restored is not None:
            sd["pending"] = [np.asarray(jax.device_get(x))
                             for x in self._restored]
        elif self._pending_pg is not None:
            sd["pending"] = [np.asarray(jax.device_get(
                g.data if hasattr(g, "data") else g))
                for _, g in self._pending_pg]
        return sd

    def set_state_dict(self, sd):
        self.steps = int(sd.get("steps", 0))
        pending = sd.get("pending")
        self._pending = None
        self._pending_pg = None
        self._restored = None if pending is None else \
            [jnp.asarray(x) for x in pending]

    def shutdown(self):
        if self._pool is not None:
            self._pool.shutdown(wait=True)
            self._pool = None
