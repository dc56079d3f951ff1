"""paddle_tpu.serving.disagg — disaggregated prefill/decode serving.

One engine doing both prefill and decode (``generate.GenerateEngine``)
couples two workloads with opposite resource shapes: prefill is a
compute-bound burst whose latency IS the user's TTFT, decode is a
steady memory-bound drip whose throughput IS the fleet's tokens/s.
Coupled, a burst of long prompts stalls every live stream's next token,
and scaling for one SLO overprovisions the other. This module splits
them into two independently-scaled pools:

* :class:`PrefillPool` — replicas of a lean :class:`PrefillEngine` that
  run the *same* bucketed prefill executables as the single engine and
  produce a **KV segment** (the ``KVCachePool.export_slot`` transport
  format) plus the request's first sampled token;
* :class:`DecodePool` — a ``MultiDecodeEngine`` whose
  :class:`~paddle_tpu.serving.generate.GenerateEngine` replicas are
  built with ``kv_import=True``: a handoff lands through
  ``KVCachePool.import_slot`` on a pre-compiled insert executable, and
  a drained decode replica's sequences migrate *with their KV*
  (``disown_inflight(export_kv=True)``) and resume mid-stream;
* :class:`DisaggServer` — the front door: admission at the prefill
  pool, a shared :class:`~paddle_tpu.serving.prefix_cache.PrefixCache`
  in front of prefill, and the explicit, *priced* handoff between the
  pools — ``planned_ms = kv_bytes / link_bandwidth()`` from the PR 12
  comm model, recorded as ``serving.handoff.{bytes,ms,queue_depth}``.

Bit-parity is the design invariant: the decode replica seats a handoff
with the exact host state single-engine prefill would have left
(``tokens=[first]``, ``length=prompt_len``, ``note_length`` ledger), so
every subsequent counter-PRNG key — a pure function of ``(request seed,
generation index)`` — is identical and the stream matches the
single-engine oracle byte for byte, through prefix hits and mid-stream
drains included.

Each pool autoscales on its own SLO via its own
:class:`~paddle_tpu.serving.supervisor.ServingSupervisor`: prefill on
``slo.ttft_p99_ms`` / queue depth (``ttft_ceiling_ms`` /
``queue_depth_ceiling``), decode on ``slo.tokens_per_s``
(``tokens_floor``). Breakers, hang failover, probes, and drains extend
per-pool unchanged — a hung prefill replica fails its queue over to
peers exactly as a hung decode replica does.
"""
from __future__ import annotations

import collections
import threading
import time

import numpy as np

from .. import monitor as _monitor
from ..io.bucketing import grow_buckets, next_bucket
from ..resilience import faults as _faults
from ..resilience.deadline import Deadline
from .admission import AdmissionController, resolve_priority
from .generate import (DecodeRequest, GenerateEngine, MultiDecodeEngine,
                       replicate_decode)
from .kv_cache import bytes_per_token, _leaves
from .multi import MultiDeviceEngine
from .prefix_cache import PrefixCache
from . import metrics
from . import reqtrace
from . import sampling as sampling_mod


class PrefillEngine:
    """Prompt ingest over one model replica: pops requests, consults
    the shared prefix cache, runs the bucketed prefill executable on a
    miss, and hands a ``(request, segment, first_token)`` triple to the
    pool's ``on_segment`` callback — the engine never owns a KV arena
    or a decode loop.

    Exposes the full ``MultiDeviceEngine`` supervision surface
    (heartbeat / probe / steal_pending / disown_inflight / requeue /
    warmup / close), so breakers, hang failover, and restart work on a
    prefill replica exactly as they do on a decode replica. A disowned
    in-flight request re-runs its prefill on the adopting replica —
    prefill is a pure function of the prompt, so the retried segment is
    identical.

    Executables: one ``("prefill", bucket)`` per prompt bucket — the
    SAME jitted body as ``GenerateEngine._get_prefill`` (kv, sampled
    first token, last-position logits) — plus one ``("psample",)``
    that re-runs the identical filter+sample math on *cached* logits,
    so a prefix hit samples its own first token (its own seed, counter
    index 0) without minting a prompt-shaped executable.
    """

    def __init__(self, model, prompt_buckets=None, max_len=512,
                 page=64, factor=2.0, queue_depth=256, deadline_ms=None,
                 shed=True, slo_goodput_floor=0.90, start=True,
                 replica_id=None, on_outcome=None, sampling=None,
                 cache=None, on_segment=None):
        import jax
        self._jax = jax
        self.model = model
        self.replica_id = replica_id
        self.on_outcome = on_outcome
        self.weights_version = 0
        self.cache = cache                  # shared PrefixCache or None
        self.on_segment = on_segment        # f(req, segment, first, hit)
        self.default_sampling = sampling_mod.resolve(sampling)
        family = grow_buckets(page, factor, max_len)
        self.max_len = int(family[-1])
        if prompt_buckets is None:
            self.prompt_buckets = tuple(family)
        else:
            pb = tuple(sorted({int(b) for b in prompt_buckets}))
            if not pb or pb[-1] > self.max_len:
                raise ValueError(
                    f"prompt_buckets {pb} must be non-empty and within "
                    f"max_len={self.max_len}")
            self.prompt_buckets = pb
        self._leaf_list = _leaves(model.kv_spec())
        self._per_token = bytes_per_token(model.kv_spec())
        self.admission = AdmissionController(
            max_queue_depth=queue_depth, default_deadline_ms=deadline_ms,
            shed=shed, slo_goodput_floor=slo_goodput_floor)
        self.admission.on_event = self._admission_event
        self._queue = collections.deque()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._current = None            # in-flight request (disownable)
        self._inflight_t0 = None
        self._exec = {}
        self._trace_count = 0
        self._stats_lock = threading.Lock()
        self._stats = {"submitted": 0, "completed": 0, "failed": 0,
                       "rejected": 0, "expired": 0, "shed": 0,
                       "prefills": 0, "prefill_tokens": 0,
                       "prefix_hits": 0, "prefix_misses": 0,
                       "compiles": 0}
        self._running = False
        self._closed = False
        self._draining = False
        self._thread = None
        self._last_progress = time.monotonic()
        self._last_ok_t = time.monotonic()
        if start:
            self.start()

    # -- client surface ----------------------------------------------------

    def make_request(self, prompt, max_new_tokens=32, eos_token=None,
                     deadline_ms=None, priority=None, trace=None,
                     sampling=None, seed=None):
        """Same validation and seed discipline as
        ``GenerateEngine.make_request`` — the request built here rides
        unchanged through handoff, so everything failover or the decode
        pool needs (resolved sampling, concrete seed, trace) is fixed
        at the front door."""
        arr = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if arr.size < 1:
            raise ValueError("empty prompt")
        if arr.size > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt of {arr.size} tokens exceeds the largest prefill "
                f"bucket {self.prompt_buckets[-1]} — raise max_len / "
                f"prompt_buckets")
        m = int(max_new_tokens)
        if m < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {m}")
        if arr.size + m > self.max_len:
            raise ValueError(
                f"prompt {arr.size} + max_new_tokens {m} exceeds the KV "
                f"arena max_len={self.max_len}")
        deadline = (Deadline.after_ms(deadline_ms)
                    if deadline_ms is not None else None)
        prio = resolve_priority(priority)
        if sampling is None and seed is None:
            params = sampling_mod.resolve(self.default_sampling)
        else:
            params = sampling_mod.resolve(sampling, seed=seed)
        if params.seed is None:
            from .generate import _fresh_seed
            params.seed = 0 if params.greedy else _fresh_seed()
        return DecodeRequest(arr, m, eos_token=eos_token,
                             deadline=deadline, priority=prio,
                             sampling=params,
                             trace=reqtrace.attach(
                                 trace, kind="decode", priority=prio,
                                 replica=self.replica_id,
                                 version=self.weights_version))

    def submit_request(self, req, admit=True):
        """The disaggregated topology's ONE admission point: the shed
        ladder runs here, before any prefill work — a request shed at
        the front door has consumed nothing."""
        with self._cond:
            if self._closed:
                raise RuntimeError("prefill engine is closed")
            if admit:
                self.admission.admit(req, len(self._queue))
            self._queue.append(req)
            depth = len(self._queue)
            self._cond.notify()
        metrics.record_submit(1)
        metrics.record_queue_depth(depth)
        if req.trace is not None:
            req.trace.hop("enqueue", replica=self.replica_id)
        with self._stats_lock:
            self._stats["submitted"] += 1
        return req.future

    def depth(self):
        with self._lock:
            return len(self._queue)

    # -- executables -------------------------------------------------------

    def _get_prefill(self, bucket):
        key = ("prefill", bucket)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        prefill_fn = self.model.prefill_fn

        def prefill(state, tokens, lengths, temps, top_ks, top_ps,
                    seeds, positions):
            self._trace_count += 1
            kv, last_logits = prefill_fn(state, tokens, lengths)
            filt = sampling_mod.filter_logits(last_logits, temps,
                                              top_ks, top_ps)
            first = sampling_mod.sample_from_filtered(filt, seeds,
                                                      positions)
            return kv, first, last_logits

        fn = jax.jit(prefill)
        self._exec[key] = fn
        self._note_compile(f"prefill[L={bucket}]")
        return fn

    def _get_psample(self):
        """First-token sampling over CACHED logits: the same
        filter+sample ops the fused prefill runs, applied to the
        logits a previous prefill stored — with the hitting request's
        own knobs, seed, and generation index 0. Tiny (``[1, V]``),
        bucket-free, minted once."""
        key = ("psample",)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax

        def psample(logits, temps, top_ks, top_ps, seeds, positions):
            self._trace_count += 1
            filt = sampling_mod.filter_logits(logits, temps, top_ks,
                                              top_ps)
            return sampling_mod.sample_from_filtered(filt, seeds,
                                                     positions)

        fn = jax.jit(psample)
        self._exec[key] = fn
        self._note_compile("psample")
        return fn

    def _note_compile(self, what):
        metrics.record_decode_compile(1, what=what)
        with self._stats_lock:
            self._stats["compiles"] += 1

    def executables(self):
        return len(self._exec), self._trace_count

    @staticmethod
    def _sampling_args(n):
        import jax.numpy as jnp
        return (jnp.zeros((n,), jnp.float32),
                jnp.zeros((n,), jnp.int32),
                jnp.ones((n,), jnp.float32),
                jnp.zeros((n,), jnp.uint32),
                jnp.zeros((n,), jnp.int32))

    def warmup(self, *_signatures):
        """Mint every executable this replica can need: one prefill per
        prompt bucket plus the psample body. Returns the number
        compiled; steady-state traffic (hits AND misses) then runs with
        zero fresh traces."""
        import jax.numpy as jnp
        before = len(self._exec)
        state = self.model.state
        samp_1 = self._sampling_args(1)
        with _monitor.trace.span("serving.prefill_warmup",
                                 buckets=len(self.prompt_buckets)):
            for lb in self.prompt_buckets:
                _kv, first, _logits = self._get_prefill(lb)(
                    state, jnp.zeros((1, lb), jnp.int32),
                    jnp.ones((1,), jnp.int32), *samp_1)
                self._jax.block_until_ready(first)
            tok = self._get_psample()(
                jnp.zeros((1, int(self.model.vocab)), jnp.float32),
                *samp_1)
            self._jax.block_until_ready(tok)
        return len(self._exec) - before

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        with self._lock:
            if self._running or self._closed:
                return
            self._running = True
            self._draining = False
            self._thread = threading.Thread(
                target=self._worker, name="paddle_tpu-serving-prefill",
                daemon=True)
            self._thread.start()

    def close(self, drain=True, timeout=None):
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._running = False
            self._draining = bool(drain)
            self._cond.notify_all()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            if timeout is None:
                timeout = 10.0 if drain else 5.0
            t.join(timeout)
        leftovers = []
        with self._cond:
            leftovers.extend(self._queue)
            self._queue.clear()
        for r in leftovers:
            r.resolve_exception(RuntimeError("prefill engine closed"))

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()

    # -- supervision surface (the MultiDeviceEngine contract) --------------

    def heartbeat(self, now=None):
        now = time.monotonic() if now is None else now
        with self._lock:
            depth = len(self._queue)
            t0 = self._inflight_t0
            active = 1 if self._current is not None else 0
        return {
            "queue_depth": depth,
            "inflight_age_s": None if t0 is None else now - t0,
            "inflight_token": t0,
            "last_progress_age_s": now - self._last_progress,
            "last_ok_age_s": now - self._last_ok_t,
            "active": active,
        }

    def probe(self, timeout_s=1.0):
        """Half-open test traffic: one smallest-bucket prefill on a
        side thread (the worker may be the stuck thing)."""
        import jax.numpy as jnp
        lb = self.prompt_buckets[0]
        if ("prefill", lb) not in self._exec:
            return None
        done = threading.Event()
        err = []

        def _go():
            try:
                fn = self._exec[("prefill", lb)]
                _kv, first, _logits = fn(
                    self.model.state, jnp.zeros((1, lb), jnp.int32),
                    jnp.ones((1,), jnp.int32), *self._sampling_args(1))
                self._jax.block_until_ready(first)
            except BaseException as e:   # noqa: BLE001 - probe verdict
                err.append(e)
            finally:
                done.set()

        threading.Thread(target=_go, daemon=True,
                         name="paddle_tpu-prefill-probe").start()
        ok = done.wait(timeout_s) and not err
        if ok:
            self._last_ok_t = time.monotonic()
        return bool(ok)

    def steal_pending(self):
        with self._cond:
            taken = list(self._queue)
            self._queue.clear()
        metrics.record_queue_depth(0)
        return taken

    def disown_inflight(self, export_kv=False):
        """Failover: hand the in-flight request (if any) to the caller.
        Prefill is a pure function of the prompt — the adopting replica
        re-runs it and produces an identical segment; if this replica's
        stuck dispatch ever completes, the ownership check in
        :meth:`_process` discards its result. ``export_kv`` is accepted
        for surface parity (nothing is resident here to export)."""
        with self._lock:
            req = self._current
            self._current = None
            self._inflight_t0 = None
        if req is None or req.future.done():
            return []
        return [req]

    def requeue(self, requests):
        if not requests:
            return
        for r in requests:
            tr = getattr(r, "trace", None)
            if tr is not None:
                tr.to("queue")
                tr.hop("requeue", replica=self.replica_id)
        with self._cond:
            if self._closed:
                for r in requests:
                    r.resolve_exception(
                        RuntimeError("prefill engine closed"))
                return
            for r in reversed(requests):
                self._queue.appendleft(r)
            depth = len(self._queue)
            self._cond.notify()
        metrics.record_queue_depth(depth)

    def _note_outcome(self, ok, exc=None):
        if ok:
            self._last_ok_t = time.monotonic()
        cb = self.on_outcome
        if cb is not None:
            try:
                cb(ok, exc)
            except Exception:   # noqa: BLE001 - observer must not kill
                pass            # the worker

    def _admission_event(self, event):
        key = {"rejected": "rejected", "expired": "expired",
               "poisoned": "failed", "shed": "shed"}.get(event)
        if key is not None:
            with self._stats_lock:
                self._stats[key] += 1

    def stats(self):
        with self._stats_lock:
            s = dict(self._stats)
        s["queue_depth"] = self.depth()
        s["executables"] = len(self._exec)
        s["traces"] = self._trace_count
        return s

    # -- the worker loop ---------------------------------------------------

    def _pop_next_locked(self, now):
        expired = []
        while self._queue:
            best_i, best_p = 0, self._queue[0].priority
            for i, r in enumerate(self._queue):
                if r.priority < best_p:
                    best_i, best_p = i, r.priority
            r = self._queue[best_i]
            del self._queue[best_i]
            if self.admission.is_expired(r, now):
                expired.append(r)
                continue
            return r, expired
        return None, expired

    def _worker(self):
        while True:
            now = time.monotonic()
            with self._cond:
                req, expired = self._pop_next_locked(now)
                depth = len(self._queue)
                if req is None and not expired:
                    if not self._running:
                        if self._draining and self._queue:
                            continue
                        return
                    self._cond.wait(0.05)
                    continue
            metrics.record_queue_depth(depth)
            for r in expired:
                self.admission.expire(r)
            if req is None:
                continue
            self._process(req)
            self._last_progress = time.monotonic()

    def _process(self, req):
        """One request: prefix lookup → (hit: psample cached logits |
        miss: bucketed prefill + cache insert) → first token →
        ``on_segment`` handoff. Ownership-checked against
        ``disown_inflight`` so a hung dispatch's late completion is
        discarded rather than double-delivered."""
        import jax.numpy as jnp
        with self._lock:
            self._current = req
            self._inflight_t0 = time.monotonic()
        tr = req.trace
        try:
            if tr is not None:
                tr.to("prefix_lookup")
            key = entry = None
            hit = False
            if self.cache is not None:
                key, entry = self.cache.lookup(req.prompt)
                hit = entry is not None
                if tr is not None:
                    tr.note_prefix(hit)
            sp = req.sampling
            samp = (jnp.asarray([sp.temperature], jnp.float32),
                    jnp.asarray([sp.top_k], jnp.int32),
                    jnp.asarray([sp.top_p], jnp.float32),
                    jnp.asarray([sp.seed or 0], jnp.uint32),
                    jnp.zeros((1,), jnp.int32))
            if hit:
                if _faults.enabled():
                    _faults.maybe_serving_fault(self.replica_id, site="prefill")
                first = int(np.asarray(self._get_psample()(
                    jnp.asarray(entry.logits), *samp))[0])
                segment = entry.segment
                # keep the entry pinned until the stream resolves: the
                # decode replica reads the leaves at seat time (and a
                # drain may re-import them later)
                req.future.add_done_callback(
                    lambda _f, c=self.cache, k=key: c.release(k))
                with self._stats_lock:
                    self._stats["prefix_hits"] += 1
            else:
                if tr is not None:
                    tr.to("prefill")
                if _faults.enabled():
                    _faults.maybe_serving_fault(self.replica_id, site="prefill")
                t0 = time.monotonic()
                p = int(req.prompt.size)
                bucket = next_bucket(p, self.prompt_buckets)
                tokens = np.zeros((1, bucket), np.int32)
                tokens[0, :p] = req.prompt
                kv, first_dev, logits = self._get_prefill(bucket)(
                    self.model.state, jnp.asarray(tokens),
                    jnp.asarray([p], jnp.int32), *samp)
                first = int(np.asarray(first_dev)[0])
                leaves = {name: np.asarray(kv[name][0])
                          for name, _tail, _dt in self._leaf_list}
                seg_bytes = sum(int(a.nbytes) for a in leaves.values())
                expected = self._per_token * bucket
                if seg_bytes != expected:
                    raise AssertionError(
                        f"prefill segment {seg_bytes} B != spec-priced "
                        f"{expected} B ({self._per_token} B/token x "
                        f"bucket {bucket})")
                segment = {"length": p, "pad": bucket,
                           "bytes": seg_bytes, "leaves": leaves}
                ms = (time.monotonic() - t0) * 1e3
                metrics.record_prefill(p, ms, bucket)
                with self._stats_lock:
                    self._stats["prefills"] += 1
                    self._stats["prefill_tokens"] += p
                    if self.cache is not None:
                        self._stats["prefix_misses"] += 1
                if self.cache is not None and key is not None:
                    self.cache.insert(key, segment, np.asarray(logits))
        except BaseException as e:   # noqa: BLE001 - to the future
            with self._lock:
                mine = self._current is req
                if mine:
                    self._current = None
                    self._inflight_t0 = None
            self._note_outcome(False, e)
            if mine:
                with self._stats_lock:
                    self._stats["failed"] += 1
                req.resolve_exception(e)
            return
        # ownership check BEFORE delivery: a disowned request was
        # already adopted (and re-prefilled) elsewhere — dropping the
        # stale result here is what makes one hang produce one handoff
        with self._lock:
            mine = self._current is req
            if mine:
                self._current = None
                self._inflight_t0 = None
        if not mine:
            return
        self._note_outcome(True)
        # the TTFT moment: prefill (or the cached-logits sample)
        # produced the stream's first real token
        if tr is not None:
            tr.first_token()
        with self._stats_lock:
            self._stats["completed"] += 1
        if self.on_segment is not None:
            try:
                self.on_segment(req, segment, first, hit)
            except BaseException as e:   # noqa: BLE001 - to the future
                with self._stats_lock:
                    self._stats["failed"] += 1
                req.resolve_exception(e)
        else:
            # standalone use (tests): resolve with the first token
            req.resolve_result(np.asarray([first], np.int32))


# ---------------------------------------------------------------------------
# the two pools


class PrefillPool(MultiDeviceEngine):
    """Breaker-aware fan-out over :class:`PrefillEngine` replicas —
    the same supervision spine as every other fleet (hang failover,
    probes, restart, scaling), with prefill's own SLO driving the
    scaling when the owner wires a supervisor with ``ttft_ceiling_ms``
    / ``queue_depth_ceiling``."""

    def __init__(self, model, devices=None, **kwargs):
        kwargs.setdefault("hedge_ms", 0)    # a prefill is not hedgeable
        #                                     work: it owns no slot, and
        #                                     re-running it is failover's
        #                                     job, not the tail's
        super().__init__(model, devices=devices, **kwargs)

    def _replicate(self, model, devices):
        return replicate_decode(model, devices)

    def _new_engine(self, model, index, on_outcome):
        return PrefillEngine(model, replica_id=index,
                             on_outcome=on_outcome,
                             **self._engine_kwargs)


class DecodePool(MultiDecodeEngine):
    """The decode side of the split: ``GenerateEngine`` replicas built
    with ``kv_import=True`` (warmup covers every capacity-family insert
    pad, so any segment lands compile-free), presets admitted without
    re-running the shed ladder, and drain migration carrying KV so a
    drained replica's streams resume mid-flight on the adopter."""

    def __init__(self, model, devices=None, **kwargs):
        kwargs["kv_import"] = True
        super().__init__(model, devices=devices, **kwargs)

    def submit_preset(self, req):
        """Land a handoff: the request already passed admission at the
        prefill pool's front door and carries its ``preset`` payload —
        route it to a healthy decode replica, ladder not re-run."""
        rep = self._pick_replica()
        if req.trace is not None:
            req.trace.hop("handoff", replica=rep.index)
            # close the handoff stage *before* the enqueue: once the
            # request is in the replica's deque its worker may seat it
            # (to("decode")) concurrently, and a later to("queue") here
            # would steal decode time back into queue. From this point
            # the wait is slot wait, not transport.
            req.trace.to("queue")
        fut = rep.engine.submit_request(req, admit=False)
        with self._hedge_lock:
            self._submitted += 1
        return fut, rep

    def _disown(self, replica):
        # drain/failover migration carries each sequence's KV segment +
        # emitted tokens: the adopter seats via _seat_preset and the
        # stream continues at the same generation index, bit-identical
        return replica.engine.disown_inflight(export_kv=True)


# ---------------------------------------------------------------------------
# the server


class DisaggServer:
    """The disaggregated topology, assembled: a shared
    :class:`PrefixCache`, a :class:`PrefillPool`, a :class:`DecodePool`,
    the priced handoff between them, and one supervisor per pool
    scaling on that pool's own SLO.

    Capacity planning rule of thumb (docs/serving.md): size the
    prefill:decode replica ratio to ``mean_prompt_tokens x arrival_rate
    / (prefill_tokens_per_s)`` vs ``mean_stream_tokens x arrival_rate /
    (decode_tokens_per_s x slots)`` — the pools saturate independently,
    which is the point of the split.

    Parameters mirror :class:`GenerateEngine` where they share meaning;
    both pools are forced onto one ``(page, factor, max_len,
    prompt_buckets)`` family so every prefill bucket has a pre-compiled
    decode-side insert executable.
    """

    def __init__(self, model, prefill_replicas=1, decode_replicas=1,
                 prefill_devices=None, decode_devices=None, slots=8,
                 page=64, factor=2.0, max_len=512, prompt_buckets=None,
                 queue_depth=256, deadline_ms=None, sampling=None,
                 prefix_cache=True, prefix_budget_bytes=64 * 1024 * 1024,
                 link_gbps=None, supervise=True,
                 supervisor_interval_s=0.25, inflight_timeout_ms=None,
                 prefill_inflight_timeout_ms=None,
                 decode_inflight_timeout_ms=None,
                 ttft_ceiling_ms=None, queue_depth_ceiling=None,
                 tokens_floor=None, prefill_initial_active=None,
                 decode_initial_active=None):
        import jax
        from ..parallel.planner import link_bandwidth, link_is_assumed
        devs = jax.local_devices()
        if prefill_devices is None:
            prefill_devices = [devs[i % len(devs)]
                               for i in range(int(prefill_replicas))]
        if decode_devices is None:
            decode_devices = [devs[i % len(devs)]
                              for i in range(int(decode_replicas))]
        family = grow_buckets(page, factor, max_len)
        if prompt_buckets is None:
            prompt_buckets = tuple(family)
        self.prompt_buckets = tuple(sorted({int(b)
                                            for b in prompt_buckets}))
        self.spec = model.kv_spec()
        self._kv_per_token = bytes_per_token(self.spec)
        self._link_bw = link_bandwidth(link_gbps)   # bytes/s
        self._link_assumed = link_is_assumed(link_gbps)
        self.prefix = (PrefixCache(self.spec,
                                   budget_bytes=prefix_budget_bytes)
                       if prefix_cache else None)
        self._lock = threading.Lock()
        self._handoffs = 0
        self._handoff_bytes = 0
        # supervision is wired EXPLICITLY per pool (below) so each
        # scales on its own SLO; the pools' built-in supervisors stay
        # off to avoid a second control loop per pool
        # hang detection is tuned per pool: a prefill dispatch is one
        # bounded executable call (tight timeouts are safe) while a
        # loaded decode tick stretches under CPU contention — one
        # shared aggressive timeout would false-positive the decode
        # fleet into failover
        if prefill_inflight_timeout_ms is None:
            prefill_inflight_timeout_ms = inflight_timeout_ms
        if decode_inflight_timeout_ms is None:
            decode_inflight_timeout_ms = inflight_timeout_ms
        self.prefill_pool = PrefillPool(
            model, devices=prefill_devices, supervise=False,
            inflight_timeout_ms=prefill_inflight_timeout_ms,
            initial_active=prefill_initial_active,
            # engine kwargs ↓
            prompt_buckets=self.prompt_buckets, max_len=max_len,
            page=page, factor=factor, queue_depth=queue_depth,
            deadline_ms=deadline_ms, sampling=sampling,
            cache=self.prefix, on_segment=self._handoff)
        self.decode_pool = DecodePool(
            model, devices=decode_devices, supervise=False,
            inflight_timeout_ms=decode_inflight_timeout_ms,
            initial_active=decode_initial_active,
            # engine kwargs ↓
            slots=slots, page=page, factor=factor, max_len=max_len,
            prompt_buckets=self.prompt_buckets,
            queue_depth=queue_depth, sampling=sampling)
        self.prefill_supervisor = None
        self.decode_supervisor = None
        if supervise:
            from .supervisor import ServingSupervisor
            # prefill scales ONLY on its own SLO (TTFT / queue depth):
            # goodput_floor 0 disables the generic branch for this
            # pool. With no ceiling configured the pool has no scale-UP
            # path either, so scaling is off entirely — otherwise the
            # idle scale-down would be a one-way ratchet that strands
            # the pool at min_replicas before traffic arrives.
            self.prefill_supervisor = ServingSupervisor(
                self.prefill_pool, interval_s=supervisor_interval_s,
                goodput_floor=0.0, ttft_ceiling_ms=ttft_ceiling_ms,
                queue_depth_ceiling=queue_depth_ceiling,
                scale=(ttft_ceiling_ms is not None
                       or queue_depth_ceiling is not None))
            # decode scales ONLY on its own SLO (tokens/s). Goodput is
            # an end-to-end signal spanning both pools — early in a
            # burst it reads 0 (submits recorded, nothing finished yet)
            # and would mis-attribute prefill backlog to decode — so
            # the generic branch is off here too.
            self.decode_supervisor = ServingSupervisor(
                self.decode_pool, interval_s=supervisor_interval_s,
                goodput_floor=0.0, tokens_floor=tokens_floor,
                scale=tokens_floor is not None)

    # -- client surface ----------------------------------------------------

    def submit(self, prompt, max_new_tokens=32, eos_token=None,
               deadline_ms=None, priority=None, trace=None,
               sampling=None, seed=None):
        """One sequence through the split topology. The future resolves
        to the generated token ids — identical, byte for byte, to what
        a single ``GenerateEngine`` returns for the same seeds."""
        rep = self.prefill_pool._pick_replica()
        req = rep.engine.make_request(
            prompt, max_new_tokens=max_new_tokens, eos_token=eos_token,
            deadline_ms=deadline_ms, priority=priority, trace=trace,
            sampling=sampling, seed=seed)
        return rep.engine.submit_request(req)

    def run(self, prompt, max_new_tokens=32, eos_token=None,
            deadline_ms=None, timeout=None, priority=None,
            sampling=None, seed=None):
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           eos_token=eos_token, deadline_ms=deadline_ms,
                           priority=priority, sampling=sampling,
                           seed=seed).result(timeout)

    # -- the handoff -------------------------------------------------------

    def _handoff(self, req, segment, first, hit):
        """Prefill (or a prefix hit) produced a segment: price the
        transfer against the PR 12 comm model, record it, and land the
        request on a decode replica as a ``preset``. Runs on the
        prefill replica's worker thread."""
        t0 = time.perf_counter()
        tr = req.trace
        if tr is not None:
            # handoff_ms covers pricing + routing + enqueue; the stage
            # closes in submit_preset (to("queue")) so decode-slot wait
            # is blamed on queue, not the link
            tr.to("handoff")
        nbytes = int(segment["bytes"])
        planned_ms = nbytes / self._link_bw * 1e3
        req.preset = {"segment": segment,
                      "tokens": [int(first)],
                      "last_token": int(first),
                      "prompt_len": int(segment["length"])}
        depth = self.decode_pool.depth() \
            if hasattr(self.decode_pool, "depth") \
            else sum(r.engine.depth()
                     for r in self.decode_pool._replicas if r.active)
        _fut, _rep = self.decode_pool.submit_preset(req)
        actual_ms = (time.perf_counter() - t0) * 1e3
        metrics.record_handoff(nbytes, planned_ms, actual_ms,
                               queue_depth=depth)
        with self._lock:
            self._handoffs += 1
            self._handoff_bytes += nbytes

    def planned_handoff_ms(self, prompt_len):
        """What the comm model predicts one handoff costs for a prompt
        of this length: per-token KV spec bytes × the prompt's bucket,
        over the link bandwidth. The smoke gate asserts recorded
        handoff bytes equal this arithmetic exactly."""
        pad = next_bucket(int(prompt_len), self.prompt_buckets)
        nbytes = self._kv_per_token * pad
        return nbytes, nbytes / self._link_bw * 1e3

    # -- lifecycle ---------------------------------------------------------

    def warmup(self):
        """Warm both pools (all prefill buckets, all decode
        executables including every capacity-family insert pad).
        Returns total fresh executables."""
        n = self.prefill_pool.warmup()
        n += self.decode_pool.warmup()
        return n

    def drain_decode_replica(self, index, reason="drain"):
        """Graceful drain of one decode replica: its live sequences
        migrate WITH their KV (``export_kv=True``) and resume
        mid-stream on peers."""
        return self.decode_pool.drain_replica(index, reason=reason)

    def close(self, drain=True, timeout=10.0):
        for sup in (self.prefill_supervisor, self.decode_supervisor):
            if sup is not None:
                sup.stop()
        if drain:
            # prefill first: stop producing new handoffs, then let the
            # decode pool run its seated streams dry
            self.prefill_pool.close(drain=True, timeout=timeout)
            self.decode_pool.drain_wait(timeout_s=timeout)
            self.decode_pool.close(drain=True, timeout=timeout)
        else:
            self.prefill_pool.close(drain=False)
            self.decode_pool.close(drain=False)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- observability -----------------------------------------------------

    def stats(self):
        with self._lock:
            handoffs = self._handoffs
            handoff_bytes = self._handoff_bytes
        out = {
            "prefill": self.prefill_pool.stats(),
            "decode": self.decode_pool.stats(),
            "handoffs": handoffs,
            "handoff_bytes": handoff_bytes,
            "kv_bytes_per_token": self._kv_per_token,
            "link_bandwidth_bps": self._link_bw,
            "link_bandwidth_assumed": self._link_assumed,
        }
        if self.prefix is not None:
            out["prefix"] = self.prefix.stats()
        return out

    def health(self, now=None):
        return {"prefill": self.prefill_pool.health(now),
                "decode": self.decode_pool.health(now)}
