"""paddle_tpu.optimizer.arena — the zero-copy flat parameter arena.

One contiguous 1-D buffer per dtype holds every trainable parameter,
with the optimizer's slot state (m/v moments, beta pows) mirrored as
equally-flat buffers in the same layout. Built ONCE at structure-version
time (the only concat the feature ever pays); afterwards the per-step
path is pure elementwise math over the flat buffers:

* the forward pass reads parameters through cached ``(offset, shape)``
  slice views of the flat buffer — XLA fuses a static slice into its
  consumer, so there is no per-step split traffic;
* gradients are packed with one ordered concat per dtype group under a
  dedicated ``arena.pack`` profile scope (the unavoidable cost of fresh
  per-leaf cotangents — NOT attributed to ``opt.*``);
* the update is one ``adam_rule`` call per group (adam_rule.py) over the
  flat buffers, with no per-step gather or scatter over the param set;
* grad-sync buckets (parallel.overlap) are CONTIGUOUS SLICES of the
  same layout (``bucket_bounds``), so exact/quantized/overlap reduce
  operates in place on the training buffers.

Coherence contract: after a flat update the per-leaf ``p.data`` payloads
are STALE until :meth:`sync_leaves` runs. Staleness is resolved lazily
at the read boundaries — ``Tensor.numpy()``, ``Layer.state_dict()``,
``CheckpointManager.save``, and any ``jit.to_static`` function that does
not itself carry the arena — through the ``tensor._arena_hook`` global,
so a training loop never pays a per-step re-scatter. Writes to a covered
parameter (``Tensor.set_value``, e.g. a checkpoint restore) mark the
arena dirty and the flat buffer repacks eagerly before the next step.

Checkpoint compatibility is bidirectional by construction:
``per_leaf_state`` emits standard ``pname@slot`` entries sliced from the
flat buffers (an arena checkpoint is indistinguishable from a per-leaf
one) and ``load_leaf_state`` scatters per-leaf checkpoints back into the
flat layout.

Scope: the arena keeps EXACT per-leaf bit-identity only while every
member steps in lockstep (the jit/SPMD training reality). Members with
*no* grad in a step are masked out (param, moments, pows untouched per
element) — the per-group beta pows are SHARED, so a member that skips
steps takes the group's bias correction, not its own (the per-leaf path
keeps one pair of pows a parameter).
"""
from __future__ import annotations

import warnings
import weakref

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor
from .. import tensor as _ptensor
from .. import monitor as _monitor
from .adam_rule import adam_rule

__all__ = ["ParamArena", "flush", "sync_all"]

# pad each dtype group to a full (8, 128) f32 tile multiple, so that
# bucket bounds and slices of the flat buffers fall on tile boundaries
ALIGN = 1024

_ALL = weakref.WeakSet()    # every live arena
_STALE = weakref.WeakSet()  # flat buffer newer than the leaf views
_DIRTY = weakref.WeakSet()  # leaf payloads newer than the flat buffer


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


def _hook(t, event):
    """Installed as ``paddle_tpu.tensor._arena_hook`` while arenas
    exist. ``read`` (Tensor.numpy) syncs stale leaves on demand;
    ``write`` (Tensor.set_value, pre-write) first pulls every leaf fresh
    so the incoming value is not clobbered by a later full sync, then
    marks the covering arena for repack."""
    if event == "read":
        for a in list(_STALE):
            if id(t) in a._pid_set:
                a.sync_leaves()
    elif event == "write":
        for a in list(_ALL):
            if id(t) in a._pid_set:
                if a in _STALE:
                    a.sync_leaves()
                _DIRTY.add(a)


def _install_hook():
    _ptensor._arena_hook = _hook


def _maybe_uninstall():
    if not _ALL:
        _ptensor._arena_hook = None


def flush_eager(layer, args, kwargs):
    """:func:`flush` at an eager ``Layer`` call — and nothing inside a
    trace, where the views are bound by jit.py and must not be touched.
    A trace shows in the payloads the forward is about to read: a
    carried arena's flat buffer, the layer's own parameters or its
    array arguments are ``jax.core.Tracer``s there."""
    if not (_STALE or _DIRTY):
        return
    if any(a.traced for a in _ALL):
        return
    payloads = [p.data for p in layer._parameters.values() if p is not None]
    payloads += jax.tree_util.tree_leaves((args, kwargs))
    if any(_is_tracer(x) for x in payloads):
        return
    flush()


def flush(exclude=()):
    """Settle all pending coherence work: repack leaf-dirty arenas
    (restored checkpoints) and sync stale leaves, except arenas in
    ``exclude`` (a compiled step's own arenas — their flat buffer IS the
    carried state, leaf staleness is free there)."""
    for a in list(_DIRTY):
        a.repack_leaves()
    ex = {id(a) for a in exclude}
    for a in list(_STALE):
        if id(a) not in ex:
            a.sync_leaves()


def sync_all():
    """Checkpoint/read-boundary helper: make every leaf view concrete."""
    flush()


class _Group:
    """One dtype's contiguous region: entries are (param, offset, size,
    shape) in parameter-list order; ``total`` includes the tile pad."""
    __slots__ = ("dtype", "tag", "entries", "total", "flat", "slots",
                 "pows")

    def __init__(self, dtype, tag):
        self.dtype = dtype
        self.tag = tag
        self.entries = []
        self.total = 0
        self.flat = None
        self.slots = {}
        self.pows = {}


class ParamArena:
    def __init__(self, params, slot_names=(), pow_names=(), adopt=None):
        """``params``: ordered trainable parameters. ``slot_names``:
        flat per-element slot buffers to mirror (e.g. moment1/moment2).
        ``pow_names``: shared per-group scalar accumulators initialised
        to 1.0 (beta pows). ``adopt``: an optimizer ``_accumulators``
        dict whose existing per-leaf slot values seed the flat buffers
        (mid-training enable)."""
        self.slot_names = tuple(slot_names)
        self.pow_names = tuple(pow_names)
        # memory_plan bf16-master: when set, bind_views casts the
        # IN-TRACE leaf views to this dtype while the flat buffer (the
        # fp32 master) stays the carried state — eager reads and
        # checkpoints keep seeing exact fp32 leaves
        self.view_dtype = None
        self.groups = []
        self._by_pid = {}   # id(param) -> (group, entry index)
        self._pid_set = set()
        by_tag = {}
        for p in params:
            dt = jnp.dtype(p.data.dtype)
            grp = by_tag.get(dt.name)
            if grp is None:
                grp = _Group(dt, dt.name)
                by_tag[dt.name] = grp
                self.groups.append(grp)
            n = int(np.prod(p.data.shape)) if p.data.shape else 1
            self._by_pid[id(p)] = (grp, len(grp.entries))
            self._pid_set.add(id(p))
            grp.entries.append((p, grp.total, n, tuple(p.data.shape)))
            grp.total += n
        adopt = adopt or {}
        pow_seed, pow_src = {}, None
        for grp in self.groups:
            pad = (-grp.total) % ALIGN
            grp.total += pad
            parts = [jnp.ravel(p.data).astype(grp.dtype)
                     for p, _, _, _ in grp.entries]
            if pad:
                parts.append(jnp.zeros((pad,), grp.dtype))
            grp.flat = Tensor(jnp.concatenate(parts) if len(parts) > 1
                              else parts[0], name=f"arena.{grp.tag}.flat")
            for sname in self.slot_names:
                buf = jnp.zeros((grp.total,), grp.dtype)
                for p, off, n, _ in grp.entries:
                    seed = adopt.get(id(p), {}).get(sname)
                    if seed is not None:
                        buf = buf.at[off:off + n].set(
                            jnp.ravel(seed.data).astype(grp.dtype))
                grp.slots[sname] = Tensor(
                    buf, name=f"arena.{grp.tag}.{sname}")
            for pname in self.pow_names:
                val = 1.0
                for p, _, _, _ in grp.entries:
                    seed = adopt.get(id(p), {}).get(pname)
                    if seed is not None:
                        val = float(jax.device_get(seed.data))
                        # keyed per (group, pow): each group carries its
                        # own pow scalar
                        pow_seed.setdefault((grp.tag, pname),
                                            set()).add(val)
                        if pow_src is None:
                            pow_src = p
                # float32 in every group: 0.999 is 1.0 in bfloat16
                grp.pows[pname] = Tensor(
                    jnp.asarray(val, jnp.float32),
                    name=f"arena.{grp.tag}.{pname}")
        if any(len(v) > 1 for v in pow_seed.values()):
            warnings.warn(
                "flat arena: adopted per-leaf beta-pow slots are not all "
                "equal (params stepped out of lockstep); the arena "
                "carries ONE shared pow per group — bias correction now "
                "follows the multi-tensor semantics", RuntimeWarning)
        self._pow_restore_seen = {}
        _ALL.add(self)
        _install_hook()
        if _monitor.enabled():
            _monitor.counter("optimizer.arena_build").inc()

    # -- identity ------------------------------------------------------------
    @property
    def param_ids(self):
        return self._pid_set

    def signature(self):
        return tuple((id(p), grp.tag, n)
                     for grp in self.groups
                     for p, _, n, _ in grp.entries)

    def matches(self, params):
        """True when ``params`` (ordered trainables) are exactly the
        members this arena was built over, same dtypes and sizes.
        Inside a traced step ``bind_views`` may have rebound the leaves
        to ``view_dtype`` casts of the fp32 master — that is this
        arena's own doing, not a membership change, so the view dtype
        counts as a match."""
        view = (jnp.dtype(self.view_dtype).name
                if self.view_dtype is not None else None)
        sig = self.signature()
        if len(params) != len(sig):
            return False
        for p, (si, sd, sn) in zip(params, sig):
            n = int(np.prod(p.data.shape)) if p.data.shape else 1
            dt = jnp.dtype(p.data.dtype).name
            if id(p) != si or n != sn or (dt != sd and dt != view):
                return False
        return True

    def holders(self):
        """name → Tensor map of every flat buffer, registered as one
        ``_accumulators`` entry so jit.to_static / the Executor carry
        them as donated state under stable names."""
        out = {}
        for grp in self.groups:
            out[f"{grp.tag}.flat"] = grp.flat
            for sname, t in grp.slots.items():
                out[f"{grp.tag}.{sname}"] = t
            for pname, t in grp.pows.items():
                out[f"{grp.tag}.{pname}"] = t
        return out

    def dissolve(self):
        _ALL.discard(self)
        _STALE.discard(self)
        _DIRTY.discard(self)
        _maybe_uninstall()

    # -- leaf view coherence -------------------------------------------------
    def bind_views(self, resave=True):
        """Point every member's ``.data`` at its slice of the (possibly
        traced) flat buffer. Returns the saved payloads for
        :meth:`unbind_views` when ``resave``; the mid-trace rebind after
        an update passes ``resave=False``."""
        saved = {} if resave else None
        for grp in self.groups:
            flat = grp.flat.data
            cast = (self.view_dtype is not None and _is_tracer(flat)
                    and jnp.dtype(self.view_dtype) != grp.dtype)
            for p, off, n, shape in grp.entries:
                if resave:
                    saved[id(p)] = (p, p.data)
                v = flat[off:off + n].reshape(shape)
                if cast:
                    # bf16 device-resident views over the fp32 master:
                    # the forward reads half-width params, grads cast
                    # back to fp32 in pack_grads, the update applies to
                    # the master. Trace-only on purpose — eager views
                    # stay exact fp32.
                    v = v.astype(self.view_dtype)
                p.data = v
        return saved

    @property
    def traced(self):
        """True while a trace has bound the flat buffers (jit.py)."""
        return any(_is_tracer(grp.flat.data) for grp in self.groups)

    def unbind_views(self, saved):
        for p, data in saved.values():
            p.data = data

    def sync_leaves(self):
        """Materialise every leaf view from the flat buffer (the lazy
        re-scatter paid only at read boundaries, never per step)."""
        if self.traced:
            self.bind_views(resave=False)
            return
        for grp in self.groups:
            flat = grp.flat.data
            for p, off, n, shape in grp.entries:
                p.data = flat[off:off + n].reshape(shape)
        _STALE.discard(self)
        if _monitor.enabled():
            _monitor.counter("optimizer.arena_leaf_sync").inc()

    def mark_stale(self):
        _STALE.add(self)

    def repack_leaves(self):
        """Rebuild the flat buffers from the leaf payloads (a restored
        checkpoint or manual ``set_value`` wrote fresh leaves)."""
        for grp in self.groups:
            if _is_tracer(grp.flat.data):
                continue
            pad = grp.total - sum(n for _, _, n, _ in grp.entries)
            parts = [jnp.ravel(p.data).astype(grp.dtype)
                     for p, _, _, _ in grp.entries]
            if pad:
                parts.append(jnp.zeros((pad,), grp.dtype))
            grp.flat.data = (jnp.concatenate(parts) if len(parts) > 1
                             else parts[0])
        _DIRTY.discard(self)
        _STALE.discard(self)
        if _monitor.enabled():
            _monitor.counter("optimizer.arena_repack").inc()

    @property
    def needs_repack(self):
        return self in _DIRTY

    def finish_step(self):
        """Post-update coherence: inside a trace, rebind the leaf views
        onto the NEW flat tracers (later in-trace reads stay
        consistent); eagerly, refresh the leaves now — eager mode has no
        write-back boundary to defer to."""
        self._pow_restore_seen.clear()
        if self.traced:
            self.bind_views(resave=False)
        else:
            self.sync_leaves()

    # -- grad packing --------------------------------------------------------
    def pack_grads(self, params_grads):
        """One ordered concat per dtype group over the step's per-leaf
        gradients (post clip/regularizer), under the ``arena.pack``
        scope so the cost ledger attributes the pack OUTSIDE ``opt.*``.
        Members without a grad this step contribute a zero segment and a
        0 mask entry (their param/moments stay untouched per element).
        Returns ``[(group, flat_grad, mask_or_None), ...]`` for live
        groups, or None when no member has a grad."""
        by_pid = {id(p): g for p, g in params_grads if g is not None}
        if not by_pid:
            return None
        _monitor.profile.register_scope("arena.pack", "op", phase="opt")
        packed = []
        with jax.named_scope("arena.pack"):
            for grp in self.groups:
                segs, flags, any_live = [], [], False
                for p, off, n, shape in grp.entries:
                    g = by_pid.get(id(p))
                    if g is None:
                        segs.append(jnp.zeros((n,), grp.dtype))
                        flags.append(False)
                    else:
                        segs.append(jnp.ravel(g).astype(grp.dtype))
                        flags.append(True)
                        any_live = True
                if not any_live:
                    continue
                pad = grp.total - sum(n for _, _, n, _ in grp.entries)
                if pad:
                    segs.append(jnp.zeros((pad,), grp.dtype))
                flat_g = (jnp.concatenate(segs) if len(segs) > 1
                          else segs[0])
                mask = None
                if not all(flags):
                    # host-side constant: 1 where the member stepped
                    m = np.zeros((grp.total,), bool)
                    for (p, off, n, _), live in zip(grp.entries, flags):
                        if live:
                            m[off:off + n] = True
                    mask = jnp.asarray(m)
                packed.append((grp, flat_g, mask))
        return packed or None

    # -- grad-sync layout ----------------------------------------------------
    def bucket_bounds(self, bucket_bytes=None, plan=None):
        """Contiguous-slice bucket plan per group for parallel.overlap:
        ``{tag: [(start, stop), ...]}`` tiles ``[0, total)`` (pad rides
        in the last bucket), each bucket one in-place slice of the flat
        gradient layout — the arena replaces plan_buckets' per-leaf
        gather with pure offsets.

        ``plan`` (a parallel.planner.MeshPlan) asserts the layout
        contract: the arena packs every member into ONE replicated
        buffer per dtype, so a plan that shards any member param would
        make these bounds non-contiguous per shard. Such a plan raises
        here instead of silently producing torn buckets — use the
        per-leaf path (arena.flat_fallback accounting) for
        tensor-sharded layouts."""
        from ..parallel.overlap import DEFAULT_BUCKET_BYTES, plan_buckets
        if plan is not None:
            named = {}
            for grp in self.groups:
                for i, (p, _off, _n, shape) in enumerate(grp.entries):
                    named[getattr(p, "name", None)
                          or f"{grp.tag}.param{i}"] = tuple(shape)
            bad = plan.arena_compatible(named)
            if bad is not None:
                raise ValueError(
                    f"mesh_plan shards arena member {bad[0]!r} as "
                    f"{bad[1]} — the flat arena requires replicated "
                    f"params; drop flat_arena or replicate the param "
                    f"in the plan")
        if bucket_bytes is None:
            bucket_bytes = DEFAULT_BUCKET_BYTES
        out = {}
        for grp in self.groups:
            sizes = [n for _, _, n, _ in grp.entries]
            idx_buckets = plan_buckets(sizes, bucket_bytes,
                                       itemsize=grp.dtype.itemsize)
            bounds = []
            for idxs in idx_buckets:
                start = grp.entries[idxs[0]][1]
                last = grp.entries[idxs[-1]]
                bounds.append((start, last[1] + last[2]))
            if bounds:
                bounds[-1] = (bounds[-1][0], grp.total)
            else:
                bounds = [(0, grp.total)]
            out[grp.tag] = bounds
        return out

    # -- checkpoint interop --------------------------------------------------
    def per_leaf_state(self, named_params):
        """Standard per-leaf ``pname@slot`` entries sliced out of the
        flat buffers — an arena checkpoint round-trips through a
        per-leaf optimizer (and vice versa) with no format marker."""
        out = {}
        for pname, p in named_params:
            hit = self._by_pid.get(id(p))
            if hit is None:
                continue
            grp, i = hit
            _, off, n, shape = grp.entries[i]
            for sname, t in grp.slots.items():
                out[f"{pname}@{sname}"] = Tensor(
                    t.data[off:off + n].reshape(shape),
                    name=f"{pname}_{sname}")
            for pow_name, t in grp.pows.items():
                # copy: a bare alias would die when the next donated
                # step consumes the pow holder's buffer
                out[f"{pname}@{pow_name}"] = Tensor(
                    jnp.array(t.data, copy=True), name=f"{pname}_{pow_name}")
        return out

    _warned_pow_restore = False

    def load_leaf_state(self, p, slot_values):
        """Scatter one param's per-leaf checkpoint slots into the flat
        layout. Beta pows restore into the shared per-group scalar; a
        non-lockstep checkpoint warns once (multi-tensor semantics)."""
        grp, i = self._by_pid[id(p)]
        _, off, n, shape = grp.entries[i]
        for sname, value in slot_values.items():
            arr = jnp.asarray(value)
            if sname in grp.slots:
                t = grp.slots[sname]
                t.data = t.data.at[off:off + n].set(
                    jnp.ravel(arr).astype(grp.dtype))
            elif sname in grp.pows:
                t = grp.pows[sname]
                new = float(jax.device_get(arr))
                # non-lockstep detection: compare against what OTHER
                # params restored into this shared scalar since the last
                # step (not against the live value — a plain resume
                # legitimately rewinds it)
                seen = self._pow_restore_seen.setdefault(
                    (grp.tag, sname), new)
                if seen != new and not ParamArena._warned_pow_restore:
                    warnings.warn(
                        "flat arena restore: per-leaf beta-pow values "
                        "differ across params; the shared per-group pow "
                        "keeps the last one (multi-tensor semantics)",
                        RuntimeWarning)
                    ParamArena._warned_pow_restore = True
                self._pow_restore_seen[(grp.tag, sname)] = new
                t.data = jnp.asarray(new, t.data.dtype)

    def leaf_slot_tensors(self, p):
        """Fresh per-leaf slot Tensors for one member (used when the
        arena is dissolved back to per-leaf mode)."""
        grp, i = self._by_pid[id(p)]
        _, off, n, shape = grp.entries[i]
        out = {}
        for sname, t in grp.slots.items():
            out[sname] = Tensor(t.data[off:off + n].reshape(shape),
                                name=f"{getattr(p, 'name', 'p')}_{sname}")
        for pow_name, t in grp.pows.items():
            out[pow_name] = Tensor(jnp.array(t.data, copy=True),
                                   name=f"{getattr(p, 'name', 'p')}"
                                        f"_{pow_name}")
        return out


# ---------------------------------------------------------------------------
# static-Executor functional path


def static_apply(opt, params_grads, param_vals, slot_vals, lr):
    """Arena update for the static Executor's functional ``run_fn``:
    params stay per-leaf (the Program's carried-state contract) but the
    m/v/pow slots live FLAT: the per-step repack is 2 gathers (p, g) + 1
    split (new p) — the slot buffers never leave the arena layout.

    ``params_grads``: the Executor's (param, grad) pairs after clip/reg;
    ``param_vals``: {id(param): current traced value};
    ``slot_vals``: {arena holder name: traced value}.
    Returns (new_param_by_pid, new_slot_vals)."""
    arena = opt._arena
    new_params, new_slots = {}, dict(slot_vals)
    by_pid = {id(p): g for p, g in params_grads if g is not None}
    for grp in arena.groups:
        segs, pparts, flags, any_live = [], [], [], False
        for p, off, n, shape in grp.entries:
            g = by_pid.get(id(p))
            pval = param_vals.get(id(p), p.data)
            pparts.append(jnp.ravel(pval).astype(grp.dtype))
            if g is None:
                segs.append(jnp.zeros((n,), grp.dtype))
                flags.append(False)
            else:
                segs.append(jnp.ravel(g).astype(grp.dtype))
                flags.append(True)
                any_live = True
        if not any_live:
            continue
        pad = grp.total - sum(n for _, _, n, _ in grp.entries)
        if pad:
            segs.append(jnp.zeros((pad,), grp.dtype))
            pparts.append(jnp.zeros((pad,), grp.dtype))
        flat_g = jnp.concatenate(segs) if len(segs) > 1 else segs[0]
        flat_p = jnp.concatenate(pparts) if len(pparts) > 1 else pparts[0]
        mask = None
        if not all(flags):
            m = np.zeros((grp.total,), bool)
            for (p, off, n, _), f in zip(grp.entries, flags):
                if f:
                    m[off:off + n] = True
            mask = jnp.asarray(m)
        b1p = slot_vals[f"{grp.tag}.beta1_pow"] * opt._beta1
        b2p = slot_vals[f"{grp.tag}.beta2_pow"] * opt._beta2
        new_p, new_m, new_v = adam_rule(
            flat_p, flat_g,
            slot_vals[f"{grp.tag}.moment1"],
            slot_vals[f"{grp.tag}.moment2"],
            lr, b1p, b2p, beta1=opt._beta1, beta2=opt._beta2,
            eps=opt._eps, weight_decay=opt._wd, mask=mask)
        new_slots[f"{grp.tag}.moment1"] = new_m
        new_slots[f"{grp.tag}.moment2"] = new_v
        new_slots[f"{grp.tag}.beta1_pow"] = b1p
        new_slots[f"{grp.tag}.beta2_pow"] = b2p
        for (p, off, n, shape), f in zip(grp.entries, flags):
            if f:
                new_params[id(p)] = new_p[off:off + n].reshape(shape)
    return new_params, new_slots
