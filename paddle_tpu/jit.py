"""paddle_tpu.jit — dygraph-to-static compilation (the TPU perf path).

TPU-native rebuild of the reference's @to_static / ProgramTranslator
(reference: python/paddle/fluid/dygraph/dygraph_to_static/* and jit.py).
The reference rewrites Python AST into a static Program; on TPU we do
something far simpler and stronger: functionalize the *state* and let
`jax.jit` trace the ordinary dygraph code into one XLA computation.

How it works: all mutable framework state (Parameters, buffers, optimizer
slots, lr, the global PRNG key) lives in Tensors. ``to_static(fn)`` swaps
every such Tensor's payload for a traced value, runs ``fn`` (the tape
records vjps on tracers; ``loss.backward()`` and ``optimizer.step()``
mutate traced payloads), then returns (outputs, new_state) from the traced
function. The result: forward + backward + optimizer update fused into a
single donated-buffer XLA executable — the shape the MXU wants.

State discovery: pass ``models=``/``optimizers=`` explicitly, or let
to_static scan the function's closure for Layers and Optimizers.
"""
from __future__ import annotations

import contextlib
import functools
import inspect

import numpy as np
import jax
import jax.numpy as jnp

from .tensor import Tensor, Parameter
from .nn.layer import Layer
from .optimizer import Optimizer
from . import random as prandom
from . import monitor as _monitor


def _discover_state_objects(fn, models, optimizers, scalers=None):
    from .amp import GradScaler
    models = list(models) if models else []
    optimizers = list(optimizers) if optimizers else []
    scalers = list(scalers) if scalers else []
    seen_m = {id(m) for m in models}
    seen_o = {id(o) for o in optimizers}
    seen_s = {id(s) for s in scalers}

    def _is_optimizer(obj):
        # a fleet.DistributedOptimizer duck-types Optimizer around `inner`
        return isinstance(obj, Optimizer) or isinstance(
            getattr(obj, "inner", None), Optimizer)

    def visit(obj):
        if isinstance(obj, Layer) and id(obj) not in seen_m:
            seen_m.add(id(obj))
            models.append(obj)
        elif _is_optimizer(obj) and id(obj) not in seen_o:
            seen_o.add(id(obj))
            optimizers.append(obj)
        elif isinstance(obj, GradScaler) and id(obj) not in seen_s:
            seen_s.add(id(obj))
            scalers.append(obj)

    target = fn
    while hasattr(target, "__wrapped__"):
        target = target.__wrapped__
    if inspect.ismethod(target):
        visit(target.__self__)
        target = target.__func__
    if getattr(target, "__closure__", None):
        for cell in target.__closure__:
            try:
                visit(cell.cell_contents)
            except ValueError:
                pass
    return models, optimizers, scalers


def _collect_state(models, optimizers, scalers=()):
    """Name → Tensor holder map for everything the step may read/mutate."""
    holders = {}
    # optimizers first: a flat-arena optimizer carries its trainables in
    # one flat buffer per dtype — those params are traced THROUGH the
    # arena (views sliced from the flat tracer), not as separate holders
    covered = set()
    for oi, o in enumerate(optimizers):
        o._ensure_all_slots()
        holders[f"o{oi}.lr"] = o._lr_tensor
        # named by position, not by id(param): the sorted names are the
        # step's argument order, and an order that follows addresses
        # gives every process its own module and compile-cache key
        for k, slots in enumerate(o._accumulators.values()):
            for sname, t in slots.items():
                holders[f"o{oi}.{k:06d}.{sname}"] = t
        arena = getattr(o, "_arena", None)
        if arena is not None:
            covered |= arena.param_ids
    for mi, m in enumerate(models):
        for name, p in m.named_parameters():
            if id(p) not in covered:
                holders[f"m{mi}.{name}"] = p
        for name, b in m.named_buffers():
            if isinstance(b, Tensor):
                holders[f"m{mi}.buf.{name}"] = b
    for si, s in enumerate(scalers):
        holders[f"s{si}.scale"] = s._scale
        holders[f"s{si}.good"] = s._good
        holders[f"s{si}.bad"] = s._bad
    holders["rng"] = prandom.global_key_tensor()
    return holders


def _arenas_of(optimizers):
    return [a for a in (getattr(o, "_arena", None) for o in optimizers)
            if a is not None]


class StaticFunction:
    """The compiled callable returned by to_static."""

    def __init__(self, fn, models=None, optimizers=None, donate_state=True,
                 jit_kwargs=None, scalers=None, bucket=False, buckets=None,
                 pad_mode="repeat", plan=None, remat=None):
        functools.update_wrapper(self, fn,
                                 assigned=("__name__", "__doc__"),
                                 updated=())
        # AST pass (reference: ProgramTranslator): converted lazily at
        # call time so ProgramTranslator.enable() flips apply dynamically
        self._orig_fn = fn
        self._converted_fn = None
        self._fn = fn
        self._models = models
        self._optimizers = optimizers
        self._scalers = scalers
        self._donate = donate_state
        self._jit_kwargs = jit_kwargs or {}
        self._cache = {}
        self._state_cache = None  # (validity key, holders, names, params)
        # shape bucketing: ragged leading (batch) dims round up to a
        # bucket so a short final batch reuses the full-batch executable
        self._bucket = bucket
        self._buckets = buckets
        self._pad_mode = pad_mode
        # parallel.planner.MeshPlan: input batches shard under the
        # plan's data spec and the plan key joins the cache key (a plan
        # switch can never silently reuse a stale executable)
        self._plan = plan
        # memory_plan remat policy: layers called inside the traced body
        # checkpoint under this ambient policy; the canonical key joins
        # the cache key so a policy toggle is exactly one recompile
        if remat is not None:
            from . import memory_plan as _mp
            remat = _mp._canon_remat(remat)
        self._remat = remat
        self._seen_base = set()  # recompile (vs first-compile) accounting

    def _resolve_objects(self):
        if self._models is None or self._optimizers is None:
            m, o, s = _discover_state_objects(self._fn, self._models,
                                              self._optimizers,
                                              self._scalers)
            self._models, self._optimizers = m, o
            if self._scalers is None:
                self._scalers = s
        elif self._scalers is None:
            # models+optimizers given explicitly: discover ONLY scalers so
            # closure objects the caller chose to exclude stay excluded
            _, _, s = _discover_state_objects(self._fn, self._models,
                                              self._optimizers, None)
            self._scalers = s
        return self._models, self._optimizers, self._scalers

    def _cached_state(self, models, optimizers, scalers):
        """The name→holder map, cached across calls: holders are stable
        Tensor objects whose .data the step swaps, so re-walking
        named_parameters()/named_buffers() every call (~17ms on
        ResNet-50) only matters when structure actually changed. Cache
        validity = the global Layer structure version + per-optimizer
        accumulator-slot counts (slots are created lazily on first
        step) + each param's stop_gradient flag (unfreezing must force
        a re-collect so _ensure_all_slots builds the new slots)."""
        from .nn.layer import struct_version

        def vkey(params):
            return (struct_version(),
                    tuple(sum(len(s) for s in o._accumulators.values())
                          for o in optimizers),
                    tuple(p.stop_gradient for p in params))

        if self._state_cache is not None and self._state_cache[0] == \
                vkey(self._state_cache[3]):
            return self._state_cache[1], self._state_cache[2], \
                self._state_cache[3]
        holders = _collect_state(models, optimizers, scalers)
        state_names = sorted(holders)
        all_params = [p for m in models for p in m.parameters()]
        # _ensure_all_slots() inside _collect_state may have created
        # slots — snapshot the validity key AFTER collection
        self._state_cache = (vkey(all_params), holders, state_names,
                             all_params)
        return holders, state_names, all_params

    def __call__(self, *args, **kwargs):
        # jit.<fn> is the parent of the step path's three spans: collect
        # (everything before the executable is called), execute (the
        # call), writeback (state and outputs); a first call's trace,
        # lowering and compile sit between them under jit.aot_capture
        with _monitor.trace.span(f"jit.{getattr(self, '__name__', 'fn')}"):
            return self._call(args, kwargs)

    def _call(self, args, kwargs):
        with _monitor.trace.span("jit.collect"):
            from .dygraph_to_static import ProgramTranslator, convert_function
            ast_on = ProgramTranslator.is_enabled()
            if ast_on:
                if self._converted_fn is None:
                    self._converted_fn = convert_function(self._orig_fn)
                self._fn = self._converted_fn
            else:
                self._fn = self._orig_fn
            models, optimizers, scalers = self._resolve_objects()
            from . import tensor as _ptensor
            if _ptensor._arena_hook is not None:
                from .optimizer import arena as _arena_mod
                # external writes to arena leaves (set_value/checkpoint
                # restore) must land in the flat buffers before we trace
                # from them; foreign arenas also sync so the step reads
                # fresh leaf data
                _arena_mod.flush(exclude=_arenas_of(optimizers))
            holders, state_names, all_params = self._cached_state(
                models, optimizers, scalers)
            # read after _cached_state: the first call builds a flat-arena
            # optimizer's arena (and installs the hook) there, and its
            # leaves go stale with this very step
            own_arenas = _arenas_of(optimizers) \
                if _ptensor._arena_hook is not None else []

            # Tensor is a pytree node, so leaves here are raw arrays / scalars.
            flat_args, treedef = jax.tree_util.tree_flatten((args, kwargs))
            arr_idx, arrays, statics = [], [], []
            for i, a in enumerate(flat_args):
                if isinstance(a, (jax.Array, np.ndarray)):
                    arrays.append(jnp.asarray(a))
                    arr_idx.append(i)
                else:
                    statics.append((i, a))

            pad_info = None
            if self._bucket and arrays and arrays[0].ndim >= 1:
                # bucket the common leading (batch) dim: every array sharing
                # it pads up to the bucket; outputs slice back after the call
                from .io.bucketing import next_bucket, pad_to_bucket
                lead = arrays[0].shape[0]
                target = next_bucket(lead, self._buckets)
                if target != lead:
                    arrays = [pad_to_bucket(a, target, mode=self._pad_mode)
                              if a.ndim >= 1 and a.shape[0] == lead else a
                              for a in arrays]
                    pad_info = (lead, target)
                    if _monitor.enabled():
                        _monitor.counter("jit.bucket_pad").inc()

            if self._plan is not None:
                arrays = [self._plan.shard_input(a) for a in arrays]

            train_flags = tuple(m.training for m in models)
            base = (treedef, tuple(arr_idx),
                    tuple((i, repr(s)) for i, s in statics), train_flags,
                    tuple(state_names), ast_on,
                    self._plan.plan_key() if self._plan is not None else None,
                    self._remat)
            key = base + (tuple((a.shape, str(a.dtype)) for a in arrays),)

            fn_label = getattr(self, "__name__", "fn")
            is_new = key not in self._cache
            if _monitor.enabled():
                if not is_new:
                    _monitor.counter("jit.cache_hit").inc()
                else:
                    _monitor.counter("jit.compile").inc()
                    if base in self._seen_base:
                        _monitor.counter("jit.recompile").inc()
            state_vals = [holders[n].data for n in state_names]
        if is_new:
            self._seen_base.add(base)
            # how many devices the step's state and inputs span, seen
            # once, when the entry is made: a GSPMD program (> 1) cannot
            # hold a Mosaic kernel, see ops.pallas.gspmd_trace
            span = max((len(sh.device_set) for sh in (
                getattr(a, "sharding", None) for a in state_vals + arrays)
                if sh is not None), default=1)
            self._cache[key] = self._make_entry(
                treedef, arr_idx, statics, state_names, span)
        entry = self._cache[key]

        if is_new and _monitor.enabled():
            # AOT the fresh entry (the compile the first call pays
            # anyway) so monitor.xla records its measured flops/bytes and,
            # under xla.trace / xla.lower / xla.backend_compile, where the
            # first call's time went; any failure keeps the jitted callable
            import time as _time
            _t0_compile = _time.perf_counter()
            with _monitor.trace.span("jit.aot_capture", fn=fn_label):
                entry["uncompiled"] = entry["jitted"]
                entry["jitted"] = _monitor.xla.aot_capture(
                    entry["jitted"], f"jit.{fn_label}",
                    (state_vals, arrays))
            # wall seconds the AOT compile cost — the goodput ledger's
            # compile category (monitor/step.py)
            _monitor.counter("jit.compile_s").inc(
                _time.perf_counter() - _t0_compile)
        with _monitor.trace.span("jit.execute"):
            try:
                out_arrays, new_state = entry["jitted"](state_vals, arrays)
            except ValueError:
                # an AOT Compiled is pinned to its capture-time input
                # shardings; when GSPMD's output sharding for a state
                # leaf drifts from its input one, the written-back state
                # no longer matches. Plain jax.jit reshards/recompiles
                # transparently — fall back to it so enabling the
                # monitor never changes trainability.
                fallback = entry.get("uncompiled")
                if fallback is None or fallback is entry["jitted"]:
                    raise
                entry["jitted"] = fallback
                if _monitor.enabled():
                    _monitor.counter("jit.aot_sharding_fallback").inc()
                out_arrays, new_state = entry["jitted"](state_vals, arrays)

        with _monitor.trace.span("jit.writeback"):
            for name, new in zip(state_names, new_state):
                holders[name].data = new
            # the flat buffers just advanced; per-leaf views now lag until a
            # read syncs them (lazily — zero per-step scatter)
            for a in own_arenas:
                a.mark_stale()
            for p in all_params:
                p._grad = None

            if pad_info is not None:
                lead, target = pad_info
                out_arrays = [o[:lead] if getattr(o, "ndim", 0) >= 1 and
                              o.shape[0] == target else o
                              for o in out_arrays]

            # rebuild outputs: arrays -> Tensors at recorded positions
            meta = entry["meta"]
            out_leaves = []
            ai = 0
            for kind, payload in meta["slots"]:
                if kind == "arr":
                    out_leaves.append(Tensor(out_arrays[ai]))
                    ai += 1
                else:
                    out_leaves.append(payload)
            return jax.tree_util.tree_unflatten(meta["treedef"], out_leaves)

    def _make_entry(self, treedef, arr_idx, statics, state_names, span=1):
        fn = self._fn
        fn_scope = getattr(self, "__name__", None) or "to_static"
        # a "root" scope is recognized by monitor.profile but never
        # counts as attribution — everything lives under it (cold path:
        # one dict write per compiled entry)
        _monitor.profile.register_scope(fn_scope, "root")
        models, optimizers = self._models, self._optimizers
        scalers = self._scalers or []
        meta = {}

        def traced(state_vals, arrays):
            flat = [None] * treedef.num_leaves
            for i, a in zip(arr_idx, arrays):
                flat[i] = a
            for i, s in statics:
                flat[i] = s
            args, kwargs = jax.tree_util.tree_unflatten(treedef, flat)

            hs = _collect_state(models, optimizers, scalers)
            arenas = [a for a in (getattr(o, "_arena", None)
                                  for o in optimizers) if a is not None]
            saved = {}
            saved_views = []
            try:
                for name, v in zip(state_names, state_vals):
                    saved[name] = hs[name].data
                    hs[name].data = v
                # arena-covered params: forward reads zero-copy views
                # sliced from the (now traced) flat buffers
                for a in arenas:
                    saved_views.append(a.bind_views())
                # tag the whole step's HLO with the function name (shows
                # up in XLA profiles / the flight recorder's HLO dump)
                with contextlib.ExitStack() as scopes:
                    if span > 1:
                        from .ops import pallas as _pallas
                        scopes.enter_context(_pallas.gspmd_trace(span))
                    if self._remat is not None:
                        from . import memory_plan as _mp
                        scopes.enter_context(_mp.remat_scope(self._remat))
                    # every compiled step carries its scopes: the
                    # labelling sites are armed on this thread while it
                    # traces, and only then (HLO metadata: Python time
                    # here, once, and nothing per step)
                    scopes.enter_context(_monitor.profile.tracing_step())
                    scopes.enter_context(jax.named_scope(fn_scope))
                    out = fn(*args, **kwargs)
                new_state = [hs[n].data for n in state_names]
                # flatten outputs treating Tensors as leaves (don't let the
                # pytree registration split them — we need to tag them)
                out_flat, out_treedef = jax.tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                slots, out_arrays = [], []
                for o in out_flat:
                    if isinstance(o, Tensor):
                        slots.append(("arr", None))
                        out_arrays.append(o.data)
                    elif isinstance(o, (jax.Array, np.ndarray)):
                        slots.append(("arr", None))
                        out_arrays.append(jnp.asarray(o))
                    else:
                        slots.append(("static", o))
                meta["slots"] = slots
                meta["treedef"] = out_treedef
                for m in models:
                    for p in m.parameters():
                        p._grad = None
                return out_arrays, new_state
            finally:
                for a, sv in zip(arenas, saved_views):
                    a.unbind_views(sv)
                for name, v in saved.items():
                    hs[name].data = v

        # the HLO module is named after the step (``jit_<fn>``), which is
        # how a device trace tells two compiled steps apart. The name is
        # also part of the compile-cache key, where op_name metadata is
        # not: an executable cached by a build that labelled nothing is
        # not mistaken for this one
        traced.__name__ = traced.__qualname__ = fn_scope
        donate = (0,) if self._donate else ()
        jitted = jax.jit(traced, donate_argnums=donate, **self._jit_kwargs)
        return {"jitted": jitted, "meta": meta}


def to_static(function=None, input_spec=None, models=None, optimizers=None,
              donate_state=True, scalers=None, bucket=False, buckets=None,
              pad_mode="repeat", plan=None, remat=None, **kwargs):
    """Decorator/wrapper: compile a dygraph step into one XLA computation.

    reference: paddle.jit.to_static (dygraph_to_static/program_translator.py)
    — functional-state tracing, preceded by the AST pass
    (dygraph_to_static.convert_function) that rewrites tensor-dependent
    python `if`/`while` into lax control flow.

    ``bucket=True`` (+ ``buckets=[...]``) pads the arrays' common leading
    dim up to a bucket size before shape-keying, so ragged final batches
    reuse the full-batch executable instead of recompiling; array outputs
    at the bucket size are sliced back to the real length. Padded rows
    repeat the last real row (``pad_mode="zeros"`` zero-fills) and DO
    participate in scalar reductions — use io.bucketing.batch_mask in the
    loss when exact ragged-batch values matter.

    ``plan=`` (a parallel.planner.MeshPlan) shards input batches under
    the plan's data axes and folds the plan key into the executable
    cache key — switching plans recompiles instead of silently reusing
    a stale layout.

    ``remat=`` (memory_plan): activation rematerialization for the
    traced body — ``"dots"``/``"full"`` or ``((pattern, policy), ...)``
    per-layer rules. Layers called inside the step checkpoint under the
    ambient policy; the policy joins the cache key, so toggling it
    recompiles exactly once instead of silently reusing an executable
    with the wrong memory shape.
    """
    def wrap(fn):
        return StaticFunction(fn, models=models, optimizers=optimizers,
                              donate_state=donate_state, scalers=scalers,
                              bucket=bucket, buckets=buckets,
                              pad_mode=pad_mode, plan=plan, remat=remat)
    if function is not None:
        return wrap(function)
    return wrap


# ---------------------------------------------------------------------------
# recompute (gradient checkpointing)

def recompute(layer_or_fn, *args, policy=None, **kwargs):
    """Run a Layer/function with rematerialization (reference:
    RecomputeOptimizer / fleet recompute; TPU-native: jax.checkpoint).

    Usage: ``out = jit.recompute(block, x)`` — activations inside `block`
    are recomputed during backward, trading FLOPs for HBM.

    ``policy=`` names what the checkpoint may keep. Unnamed (the
    default, ``memory_plan.KERNEL_RESULTS``): the inputs and a kernel's
    saved result — the flash kernels' o and statistic rows, so the
    backward replays the block without its forward flash kernel, for
    heads x rows x d_v of HBM a call; a block that holds no such call
    keeps its inputs alone. ``"full"``: only the inputs, for whoever
    needs those bytes. ``"dots"`` (checkpoint_dots): matmul outputs stay,
    the elementwise tail recomputes.
    """
    from .dispatch import apply
    from .nn.layer import bind_state, _remat_suspended
    from . import autograd as _ag
    from .memory_plan import checkpoint_policy, KERNEL_RESULTS
    policy = KERNEL_RESULTS if policy is None else policy
    ckpt_policy = checkpoint_policy(policy)
    _monitor.counter(f"recompute.placed.{policy}").inc()

    if isinstance(layer_or_fn, Layer):
        from .nn.moe import MoEFFN
        layer = layer_or_fn
        holder_map = dict(layer.named_parameters())
        for n, b in layer.named_buffers():
            if isinstance(b, Tensor):
                holder_map["buffer:" + n] = b
        names = sorted(holder_map)
        # None inputs (e.g. an absent attention mask) can't be traced —
        # record their positions and re-insert at call time
        arg_slots = [a is not None for a in args]
        live_args = tuple(a for a in args if a is not None)
        n_in = len(live_args)
        # MoE sublayers stash their aux (load-balance) loss on themselves
        # during forward — inside jax.checkpoint that Tensor would hold an
        # inner-trace tracer, so thread the aux values out as EXPLICIT
        # checkpoint outputs and re-stash them afterwards
        moe_subs = [l for l in layer.sublayers(include_self=True)
                    if isinstance(l, MoEFFN)]
        # buffers the subtree WRITES (running statistics, device
        # counters) leave the checkpoint the same way, as explicit
        # outputs, and are put back into their holders afterwards:
        # bind_state restores every holder when the body ends. Which
        # ones were written is seen while the body traces.
        buffer_names = [n for n in names if n.startswith("buffer:")]
        written = []

        def impl(rng_key, *vals):
            # the RNG key is threaded EXPLICITLY: stochastic ops inside
            # (dropout) must not advance the global key with a tracer
            # from the checkpoint trace (leak), and the backward replay
            # must regenerate identical masks
            xs, param_vals = vals[:n_in], vals[n_in:]
            it = iter(xs)
            full = [Tensor(next(it)) if live else None
                    for live in arg_slots]
            state = dict(zip(names, param_vals))
            saved = prandom._global_key.data
            prandom._global_key.data = rng_key
            try:
                # suspend the layer remat hook: the subtree is already
                # inside THIS checkpoint (re-wrapping would nest
                # checkpoints — and recurse, since the hook calls back
                # into recompute). Set inside impl so the backward
                # replay is covered too.
                with _remat_suspended():
                    with bind_state(layer, state):
                        with _ag.no_grad():
                            out = layer(*full, **kwargs)
                        written[:] = [n for n in buffer_names
                                      if holder_map[n].data is not state[n]]
                        new_buffers = tuple(holder_map[n].data
                                            for n in written)
            finally:
                prandom._global_key.data = saved
            # a layer that returns several tensors (a block's stream and
            # a loss of its own) hands each out as a checkpoint output
            n_out[:] = [len(out)] if isinstance(out, (tuple, list)) else []
            outs = tuple(o.data if isinstance(o, Tensor) else o
                         for o in (out if n_out else (out,)))
            auxs = tuple(l.aux_loss.data for l in moe_subs)
            extra = auxs + new_buffers
            return outs + extra if extra or n_out else outs[0]

        n_out = []
        ckpt = jax.checkpoint(impl, policy=ckpt_policy)
        tensors = (prandom.next_key_graph(),) + live_args + tuple(
            holder_map[n] for n in names)
        res = apply(ckpt, tensors, name="recompute")
        if not isinstance(res, tuple):
            return res
        first = n_out[0] if n_out else 1
        for l, a in zip(moe_subs, res[first:]):
            l.aux_loss = a
        for n, t in zip(written, res[first + len(moe_subs):]):
            holder_map[n].data = t.data
        return res[:first] if n_out else res[0]

    fn = layer_or_fn
    # same None-slot contract as the Layer branch: record positions of
    # None args and re-insert them at trace time
    arg_slots = [a is not None for a in args]
    live_args = tuple(a for a in args if a is not None)

    def impl(rng_key, *xs):
        # same explicit RNG threading as the Layer branch (tracer-leak +
        # backward-replay-mask invariants)
        it = iter(xs)
        full = [Tensor(next(it)) if live else None for live in arg_slots]
        saved = prandom._global_key.data
        prandom._global_key.data = rng_key
        try:
            with _remat_suspended():
                with _ag.no_grad():
                    out = fn(*full, **kwargs)
        finally:
            prandom._global_key.data = saved
        return out.data if isinstance(out, Tensor) else out

    return apply(jax.checkpoint(impl, policy=ckpt_policy),
                 (prandom.next_key_graph(),) + live_args, name="recompute")


class TracedLayer:
    """reference: fluid.dygraph.TracedLayer — trace a layer for inference."""

    def __init__(self, layer, example_inputs):
        self._layer = layer
        self._static = to_static(lambda *xs: layer(*xs), models=[layer],
                                 optimizers=[])
        self._example = example_inputs

    @staticmethod
    def trace(layer, inputs):
        tl = TracedLayer(layer, inputs)
        out = tl(*inputs)
        return out, tl

    def __call__(self, *args):
        return self._static(*args)


def save(layer, path, input_spec=None):
    """paddle.jit.save parity — delegates to io.save_inference_model."""
    from . import io as pio
    pio.save_inference_model(path, layer, input_spec=input_spec)


def load(path):
    from . import io as pio
    return pio.load_inference_model(path)
