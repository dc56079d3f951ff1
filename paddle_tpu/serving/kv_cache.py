"""paddle_tpu.serving.kv_cache — the paged KV-cache pool behind
continuous-batching decode.

Autoregressive serving lives or dies on its KV-cache discipline
(PAPERS.md: Gemma-on-TPU serving): every active sequence needs its
attention history resident on device, histories grow one token per
step, and sequences of wildly different lengths share the same decode
executable. Three constraints shape the pool:

* **Fixed slot count.** The decode batch is ``slots`` wide, always.
  A sequence occupies one slot from prefill handoff to EOS; freeing a
  slot is a host-side bookkeeping write, so a finished sequence's slot
  is refillable at the very next tick — no drain-the-batch barrier.
* **Bucketed capacity, never ragged.** Per-slot K/V storage is one
  arena per spec leaf, shaped ``[slots, capacity, *tail]``.
  ``capacity`` only ever moves along a closed
  :func:`~paddle_tpu.io.bucketing.grow_buckets` family (the *page
  schedule*): when any sequence outgrows the current capacity the whole
  arena steps to the next bucket via a pre-compiled copy. Every shape
  the pool can ever take is declared up front, so :meth:`warmup` can
  AOT-compile all of them and steady-state growth performs **zero**
  fresh compiles.
* **Budgeted, not discovered.** ``bytes()`` is exact arithmetic over
  the spec (``slots × capacity × Σ leaf bytes/token``), published as
  ``serving.decode.cache_bytes`` with headroom against the PR 12
  memory model's device budget (``monitor.memory.device_hbm_limit``) —
  the pool tells you its peak *before* you hit it, the same pre-flight
  discipline as ``memory_plan``.

The pool owns buffers and slot bookkeeping; the decode engine
(``serving/generate.py``) owns the jitted prefill/decode/insert
executables that read and write them.
"""
from __future__ import annotations

import math
import threading

import numpy as np

from ..io.bucketing import grow_buckets, next_bucket
from . import metrics


def _leaves(spec):
    """Normalize a kv spec — a dict of leaf name -> (tail_shape, dtype)
    — into a sorted list of (name, tail_shape, np.dtype)."""
    out = []
    for name in sorted(spec):
        tail, dtype = spec[name]
        out.append((name, tuple(int(d) for d in tail), np.dtype(dtype)))
    return out


def bytes_per_token(spec):
    """Exact per-token KV footprint of one sequence: the sum over spec
    leaves of ``prod(tail) * dtype.itemsize``. Accepts a single kv
    spec or a list of specs (a speculative deployment prices the
    target arena *and* the draft arena as one number — both pools
    share the slot count and page schedule, so their footprints add)."""
    if isinstance(spec, (list, tuple)):
        return sum(bytes_per_token(s) for s in spec)
    return sum(int(np.prod(tail, dtype=np.int64)) * dt.itemsize
               for _, tail, dt in _leaves(spec))


class KVCachePool:
    """Fixed-slot paged K/V arena with geometric capacity growth.

    Parameters
    ----------
    spec : dict of leaf name -> (tail_shape, dtype) — the per-token KV
        layout (e.g. ``{"k0": ((H, D), "float32"), "v0": ...}`` per
        layer). The decode model declares it (``model.kv_spec()``).
    slots : decode batch width — concurrent sequences served.
    page : smallest capacity bucket (tokens). Capacity starts here.
    factor / max_len : the geometric page schedule —
        ``grow_buckets(page, factor, max_len)``. ``max_len`` is the
        hard ceiling on prompt + generated tokens per sequence.
    """

    def __init__(self, spec, slots, page=128, factor=2.0, max_len=1024,
                 label=None):
        import jax.numpy as jnp
        self.spec = dict(spec)
        self.slots = int(slots)
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        self.label = label          # metrics namespace ("draft" for the
        #                             speculative draft arena)
        self.seq_buckets = grow_buckets(page, factor, max_len)
        self.max_len = int(self.seq_buckets[-1])
        self.capacity = int(self.seq_buckets[0])
        self._leaf_list = _leaves(self.spec)
        self.buffers = {
            name: jnp.zeros((self.slots, self.capacity) + tail, dtype=dt)
            for name, tail, dt in self._leaf_list}
        self._lock = threading.Lock()
        # owns ``buffers``: the engine's executables DONATE the arena,
        # so whoever dispatches one holds this lock from the call until
        # ``buffers`` points at the result, and every other thread that
        # reads the arena (export_slot from a drain thread) takes it —
        # a reader never sees a donated (deleted) array
        self.arena_lock = threading.RLock()
        self._free = list(range(self.slots))[::-1]   # pop() -> slot 0 first
        # per-slot live length: how many leading arena positions hold
        # *accepted* history. Readers mask by it; rollback() shrinks it.
        self._lengths = [0] * self.slots
        self._grows = 0
        self._rollbacks = 0
        self._rollback_tokens = 0
        self._publish()

    # -- slot bookkeeping --------------------------------------------------

    def alloc(self):
        """Claim a free slot index, or None when the batch is full."""
        with self._lock:
            if not self._free:
                return None
            s = self._free.pop()
            self._lengths[s] = 0
            return s

    def free(self, slot):
        """Return a slot to the pool. The stale K/V rows are left in
        place — every reader masks by live length, so a freed slot's
        garbage is never attended to, and the next prefill overwrites
        it."""
        with self._lock:
            if slot in self._free:
                raise ValueError(f"slot {slot} double-freed")
            self._free.append(int(slot))
            self._lengths[int(slot)] = 0

    def length(self, slot):
        """Live (accepted) length of one slot's history."""
        with self._lock:
            return self._lengths[int(slot)]

    def note_length(self, slot, new_len):
        """Record that arena positions ``[0, new_len)`` of ``slot`` now
        hold written history (prefill insert, decode write, or a
        speculative verify that wrote k+1 positions ahead of
        acceptance)."""
        new_len = int(new_len)
        if new_len < 0 or new_len > self.capacity:
            raise ValueError(
                f"length {new_len} outside [0, capacity={self.capacity}]")
        with self._lock:
            self._lengths[int(slot)] = new_len

    def rollback(self, slot, new_len):
        """Truncate one slot's live length to ``new_len`` WITHOUT
        freeing pages — the speculative verify-reject path: the target
        wrote k+1 positions optimistically, acceptance kept a prefix,
        and the positions past it become dead. No device data moves
        (every reader masks by length, and the next write overwrites
        in place); this is pure ledger truncation, the primitive
        prefix-cache reuse (ROADMAP item 3) will also need. Growing a
        length is note_length's job — rollback refuses it."""
        new_len = int(new_len)
        with self._lock:
            cur = self._lengths[int(slot)]
            if new_len > cur:
                raise ValueError(
                    f"rollback to {new_len} would GROW slot {slot} "
                    f"(live length {cur}) — use note_length for writes")
            if new_len < 0:
                raise ValueError(f"rollback length {new_len} < 0")
            dropped = cur - new_len
            self._lengths[int(slot)] = new_len
            self._rollbacks += 1
            self._rollback_tokens += dropped
        if dropped:
            metrics.record_rollback(dropped, label=self.label)
        return dropped

    def free_slots(self):
        with self._lock:
            return len(self._free)

    def used_slots(self):
        with self._lock:
            return self.slots - len(self._free)

    # -- slot transport (handoff / drain migration) ------------------------

    def export_slot(self, slot, pad_to=None):
        """Copy one slot's resident K/V history off the arena as a
        host-side *segment* — the one transport format shared by the
        disaggregated prefill→decode handoff and the drain-migration
        path (one tested copy primitive instead of ad-hoc tree maps).

        The segment is padded to ``pad_to`` arena positions (default:
        the slot's live length; pass a bucket so the receiving side can
        land it on a pre-compiled insert executable). Byte accounting
        is exact and asserted: the segment's payload must equal
        ``bytes_per_token(spec) × pad`` to the byte.

        Returns ``{"length", "pad", "bytes", "leaves"}`` where
        ``leaves[name]`` is a ``[pad, *tail]`` numpy array."""
        slot = int(slot)
        with self._lock:
            length = self._lengths[slot]
        pad = int(pad_to) if pad_to is not None else length
        if pad < length:
            raise ValueError(
                f"export pad {pad} < live length {length} of slot {slot}")
        if pad > self.capacity:
            raise ValueError(
                f"export pad {pad} exceeds arena capacity "
                f"{self.capacity}")
        # only the slicing needs the arena: the slices are new arrays no
        # executable donates, so the device wait happens off the lock
        with self.arena_lock:
            leaves = {name: self.buffers[name][slot, :pad]
                      for name, _tail, _dt in self._leaf_list}
        leaves = {name: np.asarray(a) for name, a in leaves.items()}
        seg_bytes = sum(int(a.nbytes) for a in leaves.values())
        expected = bytes_per_token(self.spec) * pad
        if seg_bytes != expected:
            raise AssertionError(
                f"export_slot byte accounting drifted: segment holds "
                f"{seg_bytes} bytes, spec arithmetic says {expected} "
                f"({pad} positions × {bytes_per_token(self.spec)} B/tok)")
        return {"length": length, "pad": pad, "bytes": seg_bytes,
                "leaves": leaves}

    def import_slot(self, slot, segment, insert_fn=None):
        """Land an exported segment into ``slot``: write the leaves at
        arena positions ``[0, pad)`` and record the live length through
        the :meth:`note_length` ledger (so a migrated stream's counter-
        PRNG indexing continues bit-identically).

        ``insert_fn(buffers, chunk, slot) -> buffers`` is the engine's
        pre-compiled insert executable for ``(pad, capacity)`` — the
        zero-compile path every serving import must use. Without it the
        write falls back to per-leaf ``dynamic_update_slice`` (tests,
        offline tools). Asserts the byte arithmetic on entry and that
        ``allocated_bytes()`` is unchanged by the import (a slot write
        must never resize the arena). Returns the segment bytes."""
        import jax
        import jax.numpy as jnp
        slot = int(slot)
        pad = int(segment["pad"])
        length = int(segment["length"])
        if pad > self.capacity:
            raise ValueError(
                f"segment pad {pad} exceeds arena capacity "
                f"{self.capacity} — grow first")
        leaves = segment["leaves"]
        names = {name for name, _t, _d in self._leaf_list}
        if set(leaves) != names:
            raise ValueError(
                f"segment leaves {sorted(leaves)} != spec leaves "
                f"{sorted(names)}")
        seg_bytes = sum(int(np.asarray(a).nbytes)
                        for a in leaves.values())
        expected = bytes_per_token(self.spec) * pad
        if seg_bytes != expected:
            raise AssertionError(
                f"import_slot byte accounting drifted: segment holds "
                f"{seg_bytes} bytes, spec arithmetic says {expected}")
        with self.arena_lock:
            before = self.allocated_bytes()
            if insert_fn is not None:
                chunk = {name: jnp.asarray(np.asarray(leaves[name])[None])
                         for name, _t, _d in self._leaf_list}
                self.buffers = insert_fn(self.buffers, chunk,
                                         jnp.int32(slot))
            else:
                for name, tail, _dt in self._leaf_list:
                    start = (slot, 0) + (0,) * len(tail)
                    self.buffers[name] = jax.lax.dynamic_update_slice(
                        self.buffers[name],
                        jnp.asarray(leaves[name])[None], start)
            after = self.allocated_bytes()
        if after != before:
            raise AssertionError(
                f"import_slot changed the arena footprint: "
                f"{before} -> {after} bytes")
        self.note_length(slot, length)
        return seg_bytes

    # -- capacity schedule -------------------------------------------------

    def capacity_for(self, needed_len):
        """The family bucket a sequence of ``needed_len`` tokens needs
        (raises when it exceeds ``max_len`` — admission should have
        rejected it)."""
        needed = int(needed_len)
        if needed > self.max_len:
            raise ValueError(
                f"sequence of {needed} tokens exceeds the pool's "
                f"max_len={self.max_len} (family {self.seq_buckets})")
        return next_bucket(needed, self.seq_buckets)

    def needs_growth(self, needed_len):
        return self.capacity_for(needed_len) > self.capacity

    def grow_to(self, new_capacity, grow_fn):
        """Step the arena to ``new_capacity`` (a family member) using
        ``grow_fn(buffers, old_cap, new_cap) -> buffers`` — supplied by
        the engine so the copy rides a pre-compiled executable. Pages
        are only ever added; the pool never shrinks mid-flight (slots
        churn constantly; a shrink would need a stop-the-world over
        every live sequence)."""
        new_capacity = int(new_capacity)
        if new_capacity not in self.seq_buckets:
            raise ValueError(
                f"capacity {new_capacity} is not in the bucket family "
                f"{self.seq_buckets}")
        if new_capacity <= self.capacity:
            return
        with self.arena_lock:
            self.buffers = grow_fn(self.buffers, self.capacity,
                                   new_capacity)
            self.capacity = new_capacity
        self._grows += 1
        metrics.record_cache_grow(new_capacity)
        self._publish()

    # -- budget ------------------------------------------------------------

    def bytes(self, capacity=None):
        """Exact arena footprint at ``capacity`` (default: current):
        ``slots × capacity × bytes_per_token(spec)``."""
        cap = self.capacity if capacity is None else int(capacity)
        return self.slots * cap * bytes_per_token(self.spec)

    def max_bytes(self):
        """The worst-case footprint — every slot at ``max_len``. This is
        the number to check against the HBM budget pre-flight."""
        return self.bytes(self.max_len)

    def allocated_bytes(self):
        """What the live buffers actually occupy (must equal
        :meth:`bytes` — the smoke gate's budget-honesty check)."""
        return sum(int(b.nbytes) for b in self.buffers.values())

    def headroom(self, limit_bytes=None):
        """``(limit - max_bytes, limit)`` against the device budget from
        the PR 12 memory model (``monitor.memory.device_hbm_limit``;
        override with ``limit_bytes``). ``(None, None)`` when no budget
        is known (CPU) — the pool never invents a verdict."""
        if limit_bytes is None:
            try:
                from ..monitor.memory import device_hbm_limit
                limit_bytes = device_hbm_limit()
            except Exception:
                limit_bytes = None
        if limit_bytes is None:
            return None, None
        return int(limit_bytes) - self.max_bytes(), int(limit_bytes)

    def _publish(self):
        headroom, limit = self.headroom()
        metrics.record_cache(self.bytes(), self.capacity,
                             headroom_bytes=headroom, limit_bytes=limit,
                             label=self.label)

    def stats(self):
        return {
            "slots": self.slots,
            "used_slots": self.used_slots(),
            "capacity": self.capacity,
            "max_len": self.max_len,
            "seq_buckets": list(self.seq_buckets),
            "cache_bytes": self.bytes(),
            "cache_max_bytes": self.max_bytes(),
            "grows": self._grows,
            "rollbacks": self._rollbacks,
            "rollback_tokens": self._rollback_tokens,
        }


def fits_budget(spec, slots, max_len, limit_bytes=None,
                reserve_frac=0.0):
    """Pre-flight: would a pool of ``slots × max_len`` fit under the
    device budget with ``reserve_frac`` held back for weights and
    activations? Returns (fits: bool | None, needed_bytes, limit).
    None means no budget is known — same contract as the planner's
    feasibility column. Pass ``spec`` as a list of kv specs to price a
    speculative deployment (target + draft arenas) as one pre-flight."""
    needed = int(slots) * int(max_len) * bytes_per_token(spec)
    if limit_bytes is None:
        try:
            from ..monitor.memory import device_hbm_limit
            limit_bytes = device_hbm_limit()
        except Exception:
            limit_bytes = None
    if limit_bytes is None:
        return None, needed, None
    usable = int(limit_bytes) * (1.0 - float(reserve_frac))
    return needed <= usable, needed, int(limit_bytes)


def plan_slots(spec, max_len, limit_bytes=None, reserve_frac=0.5,
               max_slots=256):
    """Inverse budget: the largest slot count whose worst-case pool
    fits in ``(1 - reserve_frac)`` of the budget. None when no budget
    is known. A list ``spec`` prices target + draft arenas together,
    so the planned slot count already pays for speculation."""
    if limit_bytes is None:
        try:
            from ..monitor.memory import device_hbm_limit
            limit_bytes = device_hbm_limit()
        except Exception:
            limit_bytes = None
    if limit_bytes is None:
        return None
    per_slot = int(max_len) * bytes_per_token(spec)
    usable = int(limit_bytes) * (1.0 - float(reserve_frac))
    return max(0, min(int(max_slots), int(math.floor(usable / per_slot))))
