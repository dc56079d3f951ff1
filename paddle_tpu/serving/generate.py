"""paddle_tpu.serving.generate — continuous-batching autoregressive
decode.

The serving tier below this module batches *fixed-shape* requests: one
request, one executable call, one future. Generative traffic is a
different animal — a request is a *sequence* that occupies capacity for
hundreds of steps, and sequences join and leave mid-flight. Batching
discipline decides tokens/s/chip (PAPERS.md: Gemma-on-TPU serving), and
the naive discipline — run a batch of sequences to completion, then
admit the next batch — wastes most of the machine: the batch runs as
long as its *longest* member, so average occupancy is roughly
``mean(len) / max(len)`` and every short sequence's slot idles until
the straggler finishes.

**Continuous batching** is the fix, and this engine implements it:

* a fixed-width decode batch of ``slots`` sequences runs **one fused
  decode step per tick** — every tick advances every live sequence by
  one token in a single pre-compiled executable;
* a finished sequence frees its slot *immediately* (a host-side
  bookkeeping write, nothing device-side moves);
* queued requests are admitted into freed slots **at the next tick** —
  there is no drain-the-batch barrier, so occupancy stays near 1.0
  under churn (the ``refill="drain"`` mode *is* the naive baseline,
  kept in-engine so the A/B in scripts/decode_loadgen.py shares every
  executable with the continuous path);
* **prefill and decode are split**: prompt ingest runs as its own
  bucketed executable (flash-attention path — prompts are the long-
  sequence work the Pallas kernel exists for), writes its KV pages into
  the slot's arena, and hands the last-token state to the decode loop.
  Decode steps never pay prompt-shaped work; prefills never stall other
  slots' decode beyond one bucket-sized call.

Shape discipline is the whole game on a compiled runtime: the engine
owns one jitted executable per (kind, bucket) key — ``decode[cap]``,
``prefill[Lb]``, ``insert[Lb, cap]``, ``grow[old→new]`` — where every
bucket comes from a closed :func:`io.bucketing.grow_buckets` family, so
:meth:`GenerateEngine.warmup` can mint *all* of them and steady-state
churn performs **zero** fresh traces (``serving.decode.compiles`` must
stay flat; scripts/decode_smoke.py gates it).

Integration, not a sidecar: requests enter through the PR 14 shed
ladder (:class:`~paddle_tpu.serving.admission.AdmissionController` —
priorities, deadlines, ``ShedError``), completions feed the ``slo.*``
goodput window so the :class:`ServingSupervisor` scales replicas off
decode traffic exactly as it does for fixed-shape traffic (plus the new
``slo.tokens_per_s`` floor), and :class:`MultiDecodeEngine` fans decode
out across breaker-guarded per-device replicas via the same
``MultiDeviceEngine`` machinery (failover, probes, restart).

**Sampling** (PR 17) rides *inside* the fused decode step: temperature
/ top-k / top-p / per-request seed enter as ``[slots]``-shaped arrays
(see serving/sampling.py), so greedy and sampled sequences share one
executable and a request's sampling config can never mint a trace.
Every random draw uses a counter-based key — a pure function of
``(request_seed, generation_index)`` — which makes a sequence's token
stream bit-reproducible across admission order, replica choice,
hedging, and failover re-prefill.

**Speculative decoding** (``draft_model=`` + ``spec_k=``): a cheap
draft model proposes ``k`` tokens autoregressively per tick (one
``lax.scan`` executable over its own :class:`KVCachePool` arena), then
the target verifies all ``k+1`` positions in one chunked step and the
accept-prefix rule (serving/sampling.py) keeps the emitted stream
*distributionally exact* against non-speculative sampling at the same
seeds. Both arenas write optimistically and roll their slot ledgers
back to the accepted prefix — pure host bookkeeping, no device copy.
On full accept the engine emits exactly ``k`` tokens and keeps the
last proposal as the next tick's input (no bonus token), which is what
holds the draft and target arenas in per-slot lockstep with zero
variable-shape catch-up work.

The model contract (duck-typed; :func:`demo_model` is the reference
implementation)::

    model.state        # pytree of device arrays (device_put per replica)
    model.vocab        # int
    model.kv_spec()    # {leaf: (tail_shape, dtype)} per cached token
    model.prefill_fn(state, tokens[B, L], lengths[B])
        -> (kv {leaf: [B, L, *tail]}, last_logits[B, V])
    model.decode_fn(state, tokens[S], kv {leaf: [S, cap, *tail]},
                    lengths[S])
        -> (logits[S, V], entry {leaf: [S, *tail]})
    model.verify_fn(state, tokens[S, C], kv, lengths[S])   # spec targets
        -> (logits[S, C, V], entry {leaf: [S, C, *tail]})

``decode_fn`` attends over ``kv[:, :lengths]`` plus the incoming
token's own K/V; the engine writes that entry at position ``lengths``
and advances the host-side length. ``verify_fn`` is the chunked
generalization (``decode_fn`` is its C == 1 special case): position
``i`` of the chunk attends over the resident history plus chunk
positions ``<= i``, and all C cache entries come back for the engine's
optimistic arena write. Only speculative *targets* need it. All slot
bookkeeping (lengths, last tokens, liveness) lives on the host and
ships as tiny arrays each tick — the only device-resident state is the
KV arena itself, so slot churn never mints an executable.
"""
from __future__ import annotations

import collections
import concurrent.futures
import itertools
import os
import threading
import time

import numpy as np

from .. import monitor as _monitor
from ..io.bucketing import next_bucket
from ..resilience import faults as _faults
from ..resilience.deadline import Deadline
from .admission import AdmissionController, resolve_priority
from .kv_cache import KVCachePool
from .multi import MultiDeviceEngine
from . import metrics
from . import reqtrace
from . import sampling as sampling_mod

_seed_counter = itertools.count(1)


def _fresh_seed():
    """Engine-assigned per-request seed (sampled requests that didn't
    pass one). Unique per process + submit order — and recorded on the
    request, so failover replay and hedge shadows reuse it verbatim."""
    return (os.getpid() * 2654435761 + next(_seed_counter)) & 0x7FFFFFFF


class DecodeRequest:
    """One sequence in flight: a prompt, a generation budget, a future
    resolving to the generated token ids (``np.int32``, EOS included
    when hit). Same resolution idempotence as ``batcher.Request`` so
    failover's first-resolution-wins contract holds."""

    __slots__ = ("prompt", "max_new_tokens", "eos_token", "n",
                 "future", "deadline", "t_enqueue", "priority", "trace",
                 "sampling", "preset")

    def __init__(self, prompt, max_new_tokens, eos_token=None,
                 deadline=None, priority=1, trace=None, sampling=None):
        self.prompt = prompt                    # 1-D int32 host array
        self.max_new_tokens = int(max_new_tokens)
        self.eos_token = eos_token
        # resolved SamplingParams with a concrete seed — the request
        # carries it so failover/hedge replay is bit-identical
        self.sampling = (sampling if sampling is not None
                         else sampling_mod.SamplingParams(seed=0))
        self.n = 1                              # one sequence
        self.future = concurrent.futures.Future()
        self.deadline = deadline
        self.priority = int(priority)
        self.t_enqueue = time.monotonic()
        # reqtrace.Attempt (None = monitor disabled); the winner of the
        # set_* race below — and only the winner — finalizes it, so a
        # hedge shadow and its primary emit one record between them
        self.trace = trace
        # disaggregated-serving payload: a dict of {"segment" (the
        # KVCachePool transport format), "tokens" emitted so far,
        # "last_token", "prompt_len"}. When set, the engine seats the
        # sequence by importing the segment instead of running prefill
        # — the handoff landing AND the KV-carrying drain-migration
        # path. None on the ordinary single-engine path.
        self.preset = None

    def age(self, now=None):
        return (now if now is not None else time.monotonic()) \
            - self.t_enqueue

    def resolve_result(self, value):
        try:
            self.future.set_result(value)
        except concurrent.futures.InvalidStateError:
            return
        if self.trace is not None:
            self.trace.finalize("ok")

    def resolve_exception(self, exc):
        try:
            self.future.set_exception(exc)
        except concurrent.futures.InvalidStateError:
            return
        if self.trace is not None:
            from .admission import DeadlineExpired, ShedError
            outcome = ("expired" if isinstance(exc, DeadlineExpired)
                       else "shed" if isinstance(exc, ShedError)
                       else "error")
            self.trace.finalize(outcome, error=repr(exc))


class _Slot:
    """Host-side state of one decode-batch lane."""

    __slots__ = ("req", "length", "tokens", "last_token", "t_seat")

    def __init__(self):
        self.req = None          # DecodeRequest occupying the lane
        self.length = 0          # tokens resident in the KV arena
        self.tokens = None       # generated so far (list of int)
        self.last_token = 0      # next decode input
        self.t_seat = 0.0        # perf_counter stamp at seating (the
        #                          slot lane's occupancy-interval start)


class GenerateEngine:
    """Continuous-batching decode over one model replica.

    Parameters
    ----------
    model : the decode-model contract above (see :func:`demo_model`).
    slots : decode batch width — sequences served concurrently.
    page / factor / max_len : the KV arena's capacity schedule
        (``grow_buckets(page, factor, max_len)``); ``max_len`` caps
        prompt + generated tokens per sequence.
    prompt_buckets : prefill length buckets (default: the capacity
        family). One prefill executable per bucket; a prompt longer
        than the largest bucket is rejected at submit.
    queue_depth / deadline_ms / shed / slo_goodput_floor : the PR 14
        admission-ladder knobs, identical semantics to
        ``ServingEngine``.
    refill : ``"continuous"`` (default — freed slots refill at the next
        tick) or ``"drain"`` (run-to-completion waves: no admission
        until *every* slot is free — the static-batching baseline the
        loadgen A/Bs against; same executables, different discipline).
    sampling : engine-default :class:`~paddle_tpu.serving.sampling.
        SamplingParams` (or dict) for submits that don't pass their
        own; None = greedy (the PR 15 behavior, bit for bit).
    draft_model : enable speculative decoding — a cheaper model of the
        SAME vocab whose proposals the target verifies. Rides its own
        :class:`KVCachePool` arena on the same page schedule. The
        target model must implement ``verify_fn``.
    spec_k : draft proposals per speculative tick (>= 1); the realized
        multiplier is ``serving.decode.spec_tokens_per_step``.
    start : launch the tick thread now (False = tests drive
        :meth:`tick` manually).
    """

    def __init__(self, model, slots=8, page=64, factor=2.0, max_len=512,
                 prompt_buckets=None, queue_depth=256, deadline_ms=None,
                 refill="continuous", shed=True, slo_goodput_floor=0.90,
                 start=True, replica_id=None, on_outcome=None,
                 sampling=None, draft_model=None, spec_k=4,
                 kv_import=False):
        import jax
        self._jax = jax
        self.model = model
        self.replica_id = replica_id
        # kv_import: this engine receives KV segments (disaggregated
        # handoff landings / KV-carrying drain migration), so warmup
        # must mint insert executables for every CAPACITY-family pad
        # too, not just the prompt buckets — a mid-stream migration's
        # segment is padded to a capacity bucket
        self.kv_import = bool(kv_import)
        # served weights version: bumped by the fleet's rolling
        # hot-swap and stamped into every request's reqtrace record
        self.weights_version = 0
        self.on_outcome = on_outcome
        if refill not in ("continuous", "drain"):
            raise ValueError(
                f"refill must be 'continuous' or 'drain', got {refill!r}")
        self.refill = refill
        self.default_sampling = sampling_mod.resolve(sampling)
        self.pool = KVCachePool(model.kv_spec(), slots, page=page,
                                factor=factor, max_len=max_len)
        self.slots = self.pool.slots
        self.max_len = self.pool.max_len
        self.spec_k = int(spec_k)
        self.draft_model = draft_model
        self.draft_pool = None
        self._draft_state = None
        if draft_model is not None:
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            if int(draft_model.vocab) != int(model.vocab):
                raise ValueError(
                    f"draft vocab {draft_model.vocab} != target vocab "
                    f"{model.vocab} — the accept rule compares "
                    f"distributions over one vocabulary")
            if not hasattr(model, "verify_fn"):
                raise ValueError(
                    "speculative decoding needs model.verify_fn "
                    "(chunked decode) on the TARGET model")
            # the draft arena shares the slot count and page schedule,
            # so _ensure_capacity can grow both pools in lockstep and
            # plan_slots([target_spec, draft_spec], ...) prices the pair
            self.draft_pool = KVCachePool(
                draft_model.kv_spec(), slots, page=page, factor=factor,
                max_len=max_len, label="draft")
            dstate = draft_model.state
            dev = getattr(model, "device", None)
            if dev is not None:
                # fleet replicas share one draft object; pin a state
                # copy next to this replica's target weights
                dstate = jax.device_put(dstate, dev)
            self._draft_state = dstate
        if prompt_buckets is None:
            self.prompt_buckets = tuple(self.pool.seq_buckets)
        else:
            pb = tuple(sorted({int(b) for b in prompt_buckets}))
            if not pb or pb[-1] > self.max_len:
                raise ValueError(
                    f"prompt_buckets {pb} must be non-empty and within "
                    f"max_len={self.max_len}")
            self.prompt_buckets = pb
        self.admission = AdmissionController(
            max_queue_depth=queue_depth, default_deadline_ms=deadline_ms,
            shed=shed, slo_goodput_floor=slo_goodput_floor)
        self.admission.on_event = self._admission_event
        self._queue = collections.deque()
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._slots = [_Slot() for _ in range(self.slots)]
        # Chrome-export resource-lane prefix: one lane per KV slot
        # ("kv.slot3", or "kv1.slot3" inside a fleet)
        self._lane = ("kv" if replica_id is None
                      else f"kv{replica_id}")
        # (kind, *buckets) -> jitted executable; single-writer (the tick
        # thread / warmup), so no lock — reads are atomic dict gets
        self._exec = {}
        # incremented INSIDE jitted bodies at trace time: any retrace —
        # even one that reuses an existing key — moves this counter, so
        # the zero-recompile gate catches dtype/shape drift too
        self._trace_count = 0
        self._stats_lock = threading.Lock()
        self._stats = {"submitted": 0, "completed": 0, "failed": 0,
                       "rejected": 0, "expired": 0, "shed": 0,
                       "ticks": 0, "tokens": 0, "prefills": 0,
                       "prefill_tokens": 0, "compiles": 0, "grows": 0,
                       "draft_steps": 0, "verify_steps": 0,
                       "spec_proposed": 0, "spec_accepted": 0,
                       "kv_imports": 0}
        self._occupancy_sum = 0.0
        self._running = False
        self._closed = False
        self._draining = False
        self._thread = None
        self._tick_t0 = None
        self._last_progress = time.monotonic()
        self._last_ok_t = time.monotonic()
        import weakref
        from ..monitor import sampler as _sampler
        ref = weakref.ref(self)

        def _depth_series():
            eng = ref()
            if eng is None:
                return None
            return {"serving.queue_depth": eng.depth()}

        self._sampler_key = _sampler.register_provider(
            f"serving-generate-{id(self)}", _depth_series)
        if start:
            self.start()

    # -- client surface ----------------------------------------------------

    def make_request(self, prompt, max_new_tokens=32, eos_token=None,
                     deadline_ms=None, priority=None, trace=None,
                     sampling=None, seed=None):
        """Validate one submit into a :class:`DecodeRequest` (not yet
        enqueued — the fleet wrapper builds once, then routes). Pass a
        shed request's ``RequestTrace`` as ``trace=`` when re-submitting
        so the retry folds into the same ``serving.request`` record.

        ``sampling`` is None (engine default; greedy unless the engine
        was built with one), a dict of knobs, or
        :class:`~paddle_tpu.serving.sampling.SamplingParams`; ``seed``
        overrides its per-request seed. A sampled request with no seed
        gets a fresh one HERE, so the request object carries everything
        failover or a hedge shadow needs to replay the exact stream."""
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
            params.seed = 0 if params.greedy else _fresh_seed()
        return DecodeRequest(arr, m, eos_token=eos_token,
                             deadline=deadline, priority=prio,
                             sampling=params,
                             trace=reqtrace.attach(
                                 trace, kind="decode", priority=prio,
                                 replica=self.replica_id,
                                 version=self.weights_version))

    def submit_request(self, req, admit=True):
        """Admit + enqueue; returns the future. Raises ``ShedError`` /
        ``QueueFullError`` from the admission ladder. ``admit=False``
        skips the ladder — for a disaggregated handoff the request was
        admitted once at the prefill pool's front door and must not be
        double-charged (or shed after its prefill already ran)."""
        with self._cond:
            if self._closed:
                raise RuntimeError("decode engine is closed")
            if admit:
                self.admission.admit(req, len(self._queue))
            self._queue.append(req)
            depth = len(self._queue)
            self._cond.notify()
        metrics.record_submit(1)
        metrics.record_queue_depth(depth)
        if req.trace is not None:
            req.trace.hop("enqueue", replica=self.replica_id)
            if _monitor.trace.enabled():
                with _monitor.trace.span("serving.enqueue", depth=depth):
                    reqtrace.flow_mark(req.trace)
        with self._stats_lock:
            self._stats["submitted"] += 1
        return req.future

    def submit(self, prompt, max_new_tokens=32, eos_token=None,
               deadline_ms=None, priority=None, trace=None,
               sampling=None, seed=None):
        """Enqueue one sequence; the future resolves to the generated
        token ids (``np.int32``; the first token comes from the prefill
        itself, EOS — when given and hit — is included and terminal)."""
        return self.submit_request(self.make_request(
            prompt, max_new_tokens=max_new_tokens, eos_token=eos_token,
            deadline_ms=deadline_ms, priority=priority, trace=trace,
            sampling=sampling, seed=seed))

    def run(self, prompt, max_new_tokens=32, eos_token=None,
            deadline_ms=None, timeout=None, priority=None,
            sampling=None, seed=None):
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           eos_token=eos_token,
                           deadline_ms=deadline_ms,
                           priority=priority, sampling=sampling,
                           seed=seed).result(timeout)

    def depth(self):
        with self._lock:
            return len(self._queue)

    # -- executables -------------------------------------------------------
    #
    # Every jitted body bumps _trace_count at TRACE time (the increment
    # is a host side effect, re-executed only when XLA retraces), so
    # executables() exposes both the key count and the honest trace
    # count — the smoke gate pins the latter after warmup.

    @staticmethod
    def _masked_write(jnp, buffers, entry, rows, pos, active, n_slots):
        """Scatter per-slot cache entries at ``pos`` into the arena,
        masked by ``active`` (inactive lanes keep their old rows). The
        mask rides on the scattered VALUES — gather the old rows, blend,
        one scatter — so the whole-arena update stays a single aliasable
        write (the executables donate their arena argument; a masked
        ``jnp.where`` over the full buffer would force two copies)."""
        out = {}
        for name, buf in buffers.items():
            old = buf[rows, pos]
            mask = active.reshape((n_slots,) + (1,) * (old.ndim - 1))
            out[name] = buf.at[rows, pos].set(
                jnp.where(mask, entry[name], old))
        return out

    def _get_decode(self, cap):
        key = ("decode", cap)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        jnp = jax.numpy
        decode_fn = self.model.decode_fn
        n_slots = self.slots

        def step(state, buffers, tokens, lengths, active,
                 temps, top_ks, top_ps, seeds, positions):
            self._trace_count += 1
            logits, entry = decode_fn(state, tokens, buffers, lengths)
            filt = sampling_mod.filter_logits(logits, temps, top_ks,
                                              top_ps)
            nxt = sampling_mod.sample_from_filtered(filt, seeds,
                                                    positions)
            pos = jnp.minimum(lengths, cap - 1)
            rows = jnp.arange(n_slots)
            out = self._masked_write(jnp, buffers, entry, rows, pos,
                                     active, n_slots)
            return nxt, out

        # the caller always replaces pool.buffers with the result, so
        # the arena is donated — the scatter updates in place instead
        # of copying slots × capacity × spec bytes every token
        fn = jax.jit(step, donate_argnums=(1,))
        self._exec[key] = fn
        self._note_compile(f"decode[cap={cap}]")
        return fn

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
            # last_logits ride out for the disaggregated prefix cache
            # (a later hit re-samples its own first token from them)
            return kv, first, last_logits

        fn = jax.jit(prefill)
        self._exec[key] = fn
        self._note_compile(f"prefill[L={bucket}]")
        return fn

    def _get_draft_prefill(self, bucket):
        """Draft-arena prompt ingest: the draft's KV only — the first
        token is the target prefill's to sample."""
        key = ("dprefill", bucket)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        prefill_fn = self.draft_model.prefill_fn

        def prefill(dstate, tokens, lengths):
            self._trace_count += 1
            kv, _last = prefill_fn(dstate, tokens, lengths)
            return kv

        fn = jax.jit(prefill)
        self._exec[key] = fn
        self._note_compile(f"dprefill[L={bucket}]")
        return fn

    def _get_insert(self, bucket, cap, kind="insert"):
        key = (kind, bucket, cap)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax

        def insert(buffers, chunk, slot):
            self._trace_count += 1
            out = {}
            for name, buf in buffers.items():
                start = (slot,) + (0,) * (buf.ndim - 1)
                out[name] = jax.lax.dynamic_update_slice(
                    buf, chunk[name], start)
            return out

        fn = jax.jit(insert, donate_argnums=(0,))
        self._exec[key] = fn
        self._note_compile(f"{kind}[L={bucket}, cap={cap}]")
        return fn

    def _get_grow(self, old_cap, new_cap, kind="grow"):
        key = (kind, old_cap, new_cap)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        jnp = jax.numpy
        extra = new_cap - old_cap

        def grow(buffers):
            self._trace_count += 1
            out = {}
            for name, buf in buffers.items():
                pad = [(0, 0)] * buf.ndim
                pad[1] = (0, extra)
                out[name] = jnp.pad(buf, pad)
            return out

        fn = jax.jit(grow)
        self._exec[key] = fn
        self._note_compile(f"{kind}[{old_cap}->{new_cap}]")
        return fn

    def _get_spec_draft(self, cap):
        """The draft proposal loop: k autoregressive draft steps as one
        executable (``lax.scan``, so k never multiplies dispatches).
        Proposal ``i`` is drawn from the filtered draft distribution
        with the SAME ``(seed, pos0+i, SALT_TOKEN)`` key the
        non-speculative path would use at that generation index — that
        identity is what makes a self-draft reproduce the
        non-speculative stream. Returns ``(proposals[S, k],
        q_probs[S, k, V], updated draft buffers)``."""
        key = ("sdraft", cap)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        jnp = jax.numpy
        draft_fn = self.draft_model.decode_fn
        n_slots = self.slots
        k = self.spec_k

        def propose(dstate, dbufs, tokens, lengths, active,
                    temps, top_ks, top_ps, seeds, pos0):
            self._trace_count += 1
            rows = jnp.arange(n_slots)

            def body(carry, i):
                bufs, tok, ln = carry
                logits, entry = draft_fn(dstate, tok, bufs, ln)
                filt = sampling_mod.filter_logits(logits, temps,
                                                  top_ks, top_ps)
                d = sampling_mod.sample_from_filtered(filt, seeds,
                                                      pos0 + i)
                q = sampling_mod.probs_from_filtered(filt)
                pos = jnp.minimum(ln, cap - 1)
                bufs = self._masked_write(jnp, bufs, entry, rows, pos,
                                          active, n_slots)
                return (bufs, d, ln + 1), (d, q)

            (bufs, _tok, _ln), (ds, qs) = jax.lax.scan(
                body, (dbufs, tokens, lengths), jnp.arange(k))
            return (jnp.transpose(ds, (1, 0)),
                    jnp.transpose(qs, (1, 0, 2)), bufs)

        fn = jax.jit(propose, donate_argnums=(1,))
        self._exec[key] = fn
        self._note_compile(f"sdraft[cap={cap}, k={k}]")
        return fn

    def _get_verify(self, cap):
        """The target's batched verify: one chunked forward over
        ``[last, d_1 .. d_k]`` evaluates all k+1 positions, writes the
        k+1 cache entries optimistically (the host ledger rolls back to
        the accepted prefix), and runs the accept-prefix rule in-graph.
        Returns ``(n_accepted[S], resampled[S], updated buffers)``."""
        key = ("verify", cap)
        fn = self._exec.get(key)
        if fn is not None:
            return fn
        jax = self._jax
        jnp = jax.numpy
        verify_fn = self.model.verify_fn
        n_slots = self.slots
        k = self.spec_k

        def verify(state, buffers, chunk, lengths, active,
                   temps, top_ks, top_ps, seeds, pos0, proposals,
                   q_probs):
            self._trace_count += 1
            logits, entry = verify_fn(state, chunk, buffers, lengths)
            rows = jnp.arange(n_slots)
            pos = jnp.minimum(
                lengths[:, None] + jnp.arange(k + 1)[None, :], cap - 1)
            out = self._masked_write(jnp, buffers, entry, rows[:, None],
                                     pos, active, n_slots)
            # filter all k+1 target distributions with this slot's knobs
            flat = logits.reshape(n_slots * (k + 1), -1)
            rep = lambda a: jnp.repeat(a, k + 1)    # noqa: E731
            p_flat = sampling_mod.probs_from_filtered(
                sampling_mod.filter_logits(flat, rep(temps),
                                           rep(top_ks), rep(top_ps)))
            p_probs = p_flat.reshape(n_slots, k + 1, -1)
            a, resampled = sampling_mod.accept_prefix(
                p_probs, q_probs, proposals, seeds, pos0)
            return a, resampled, out

        fn = jax.jit(verify, donate_argnums=(1,))
        self._exec[key] = fn
        self._note_compile(f"verify[cap={cap}, k={k}]")
        return fn

    def _note_compile(self, what):
        metrics.record_decode_compile(1, what=what)
        with self._stats_lock:
            self._stats["compiles"] += 1

    def executables(self):
        """(executable count, trace count) — both must stay flat after
        :meth:`warmup` across any amount of join/leave churn."""
        return len(self._exec), self._trace_count

    def _sampling_args(self, n):
        """Zero-valued (= greedy) sampling arrays of batch width ``n``
        for warmup and probe calls — shapes and dtypes must match the
        live tick's exactly or the zero-retrace gate trips."""
        import jax.numpy as jnp
        return (jnp.zeros((n,), jnp.float32),    # temps
                jnp.zeros((n,), jnp.int32),      # top_ks
                jnp.ones((n,), jnp.float32),     # top_ps
                jnp.zeros((n,), jnp.uint32),     # seeds
                jnp.zeros((n,), jnp.int32))      # positions

    def warmup(self, *_signatures):
        """Mint and trace every executable the engine can ever need:
        one decode step per capacity bucket, one grow per consecutive
        bucket pair, one prefill per prompt bucket, and one insert per
        (prompt bucket, capacity) pair that can co-occur — plus, when a
        draft model is mounted, the speculative family (draft prefill /
        insert / grow per the same buckets, and one draft-scan + verify
        pair per capacity). After this, steady-state churn — including
        cache growth and any accept/reject pattern — runs entirely on
        cached executables. Returns the number compiled. (Positional
        signatures from the fleet wrapper are accepted and ignored —
        a decode engine's shapes come from its bucket families.)"""
        import jax.numpy as jnp
        before = len(self._exec)
        family = self.pool.seq_buckets
        spec = self.pool._leaf_list
        state = self.model.state
        tokens_s = jnp.zeros((self.slots,), jnp.int32)
        ones_s = jnp.ones((self.slots,), jnp.int32)
        active = jnp.zeros((self.slots,), bool)
        samp_s = self._sampling_args(self.slots)
        samp_1 = self._sampling_args(1)
        speculative = self.draft_model is not None
        dspec = self.draft_pool._leaf_list if speculative else None

        def zeros_arena(leaf_list, cap):
            # fresh per donating call — the executables consume (donate)
            # their arena argument, so a shared warmup buffer would be
            # a use-after-donate
            return {name: jnp.zeros((self.slots, cap) + tail, dt)
                    for name, tail, dt in leaf_list}

        with _monitor.trace.span("serving.warmup",
                                 buckets=len(family)):
            insert_pads = set(self.prompt_buckets)
            if self.kv_import:
                insert_pads |= set(family)
            for cap in family:
                nxt, out = self._get_decode(cap)(
                    state, zeros_arena(spec, cap), tokens_s, ones_s,
                    active, *samp_s)
                self._jax.block_until_ready(nxt)
                for lb in sorted(insert_pads):
                    if lb > cap:
                        continue
                    chunk = {name: jnp.zeros((1, lb) + tail, dt)
                             for name, tail, dt in spec}
                    self._jax.block_until_ready(self._get_insert(lb, cap)(
                        zeros_arena(spec, cap), chunk, jnp.int32(0)))
                if speculative:
                    ds, qs, _ = self._get_spec_draft(cap)(
                        self._draft_state, zeros_arena(dspec, cap),
                        tokens_s, ones_s, active, *samp_s)
                    self._jax.block_until_ready(ds)
                    k = self.spec_k
                    a, t, _ = self._get_verify(cap)(
                        state, zeros_arena(spec, cap),
                        jnp.zeros((self.slots, k + 1), jnp.int32),
                        ones_s, active, *samp_s,
                        jnp.zeros((self.slots, k), jnp.int32),
                        jnp.zeros((self.slots, k, self.model.vocab),
                                  jnp.float32))
                    self._jax.block_until_ready(a)
                    for lb in self.prompt_buckets:
                        if lb > cap:
                            continue
                        dchunk = {name: jnp.zeros((1, lb) + tail, dt)
                                  for name, tail, dt in dspec}
                        self._jax.block_until_ready(
                            self._get_insert(lb, cap, kind="dinsert")(
                                zeros_arena(dspec, cap), dchunk,
                                jnp.int32(0)))
            for old, new in zip(family, family[1:]):
                bufs = {name: jnp.zeros((self.slots, old) + tail, dt)
                        for name, tail, dt in spec}
                self._jax.block_until_ready(self._get_grow(old, new)(bufs))
                if speculative:
                    dbufs = {name: jnp.zeros((self.slots, old) + tail,
                                             dt)
                             for name, tail, dt in dspec}
                    self._jax.block_until_ready(
                        self._get_grow(old, new, kind="dgrow")(dbufs))
            for lb in self.prompt_buckets:
                kv, first, _logits = self._get_prefill(lb)(
                    state, jnp.zeros((1, lb), jnp.int32),
                    jnp.ones((1,), jnp.int32), *samp_1)
                self._jax.block_until_ready(first)
                if speculative:
                    dkv = self._get_draft_prefill(lb)(
                        self._draft_state, jnp.zeros((1, lb), jnp.int32),
                        jnp.ones((1,), jnp.int32))
                    self._jax.block_until_ready(
                        next(iter(dkv.values())))
        return len(self._exec) - before

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        with self._lock:
            if self._running or self._closed:
                return
            self._running = True
            self._draining = False
            self._thread = threading.Thread(
                target=self._worker, name="paddle_tpu-serving-decode",
                daemon=True)
            self._thread.start()

    def close(self, drain=True, timeout=None):
        """Stop the tick thread. ``drain=True`` keeps ticking until the
        queue and every slot are empty (bounded join); anything left
        after the join fails with RuntimeError — a future is never
        silently lost."""
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
            for s, slot in enumerate(self._slots):
                if slot.req is not None:
                    leftovers.append(slot.req)
                    slot.req = None
                    self.pool.free(s)
                    if self.draft_pool is not None:
                        self.draft_pool.note_length(s, 0)
        for r in leftovers:
            r.resolve_exception(RuntimeError("decode engine closed"))
        from ..monitor import sampler as _sampler
        _sampler.unregister_provider(self._sampler_key)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()

    # -- supervision surface (the MultiDeviceEngine contract) --------------

    def heartbeat(self, now=None):
        now = time.monotonic() if now is None else now
        with self._lock:
            t0 = self._tick_t0
            depth = len(self._queue)
            seated = sum(1 for s in self._slots if s.req is not None)
        return {
            "queue_depth": depth,
            "inflight_age_s": None if t0 is None else now - t0,
            "inflight_token": t0,
            "last_progress_age_s": now - self._last_progress,
            "last_ok_age_s": now - self._last_ok_t,
            # seated (still-generating) sequences — what a drain waits
            # to hit zero
            "active": seated,
        }

    def probe(self, timeout_s=1.0):
        """Half-open test traffic: run the decode executable (or, on a
        speculative engine, the verify executable) on an all-inactive
        batch on a side thread (the tick thread may be the thing that's
        stuck) and report whether it finished in time."""
        import jax.numpy as jnp
        cap = self.pool.capacity
        kind = ("decode" if ("decode", cap) in self._exec
                else "verify" if ("verify", cap) in self._exec
                else None)
        if kind is None:
            return None          # never warmed / served — nothing to test
        done = threading.Event()
        err = []

        def _go():
            try:
                fn = self._exec[(kind, cap)]
                zeros = jnp.zeros((self.slots,), jnp.int32)
                inactive = jnp.zeros((self.slots,), bool)
                samp = self._sampling_args(self.slots)
                # a throwaway arena, NOT pool.buffers — the executable
                # donates (consumes) its arena argument, and the live
                # pool must survive the probe
                bufs = {name: jnp.zeros(
                            (self.slots, self.pool.capacity) + tail, dt)
                        for name, tail, dt in self.pool._leaf_list}
                if kind == "decode":
                    nxt, _ = fn(self.model.state, bufs,
                                zeros, zeros, inactive, *samp)
                else:
                    k = self.spec_k
                    nxt, _t, _b = fn(
                        self.model.state, bufs,
                        jnp.zeros((self.slots, k + 1), jnp.int32),
                        zeros, inactive, *samp,
                        jnp.zeros((self.slots, k), jnp.int32),
                        jnp.zeros((self.slots, k, self.model.vocab),
                                  jnp.float32))
                self._jax.block_until_ready(nxt)
            except BaseException as e:   # noqa: BLE001 - probe verdict
                err.append(e)
            finally:
                done.set()

        threading.Thread(target=_go, daemon=True,
                         name="paddle_tpu-decode-probe").start()
        ok = done.wait(timeout_s) and not err
        if ok:
            self._last_ok_t = time.monotonic()
        return bool(ok)

    def steal_pending(self):
        """Failover: hand every queued request to the caller."""
        with self._cond:
            taken = list(self._queue)
            self._queue.clear()
        metrics.record_queue_depth(0)
        return taken

    def disown_inflight(self, export_kv=False):
        """Failover: evict every live sequence and hand its request
        over. Partial output is discarded — decode is a pure function
        of the request (greedy argmax, or counter-based sampling keys
        derived from the request's own ``(seed, generation_index)``),
        so the adopting replica's re-prefill regenerates a
        bit-identical stream from the prompt, speculative or not
        (first resolution wins either way).

        ``export_kv=True`` (the disaggregated decode pool's drain path)
        instead carries each sequence's resident KV off the arena via
        :meth:`KVCachePool.export_slot` — padded to its capacity-family
        bucket so the adopter lands it on a warmed insert executable —
        along with the tokens emitted so far, so the adopting replica
        resumes mid-stream (same ledger length, same generation index:
        bit-identical continuation) instead of re-running prefill."""
        taken = []
        evicted = []
        with self._lock:
            for s, slot in enumerate(self._slots):
                if slot.req is not None:
                    if export_kv and slot.length > 0:
                        seg = self.pool.export_slot(
                            s, pad_to=self.pool.capacity_for(
                                slot.length))
                        slot.req.preset = {
                            "segment": seg,
                            "tokens": list(slot.tokens),
                            "last_token": slot.last_token,
                            "prompt_len": int(slot.req.prompt.size),
                        }
                    taken.append(slot.req)
                    evicted.append((s, slot.t_seat))
                    slot.req = None
                    slot.tokens = None
                    self.pool.free(s)
                    if self.draft_pool is not None:
                        self.draft_pool.note_length(s, 0)
        trc = _monitor.trace
        if trc.enabled() and evicted:
            now_pc = time.perf_counter()
            for s, t_seat in evicted:
                trc.lane_complete(f"{self._lane}.slot{s}", "req evicted",
                                  t_seat, now_pc)
        return taken

    def requeue(self, requests):
        """Failover re-dispatch: front-of-queue, no re-admission."""
        if not requests:
            return
        for r in requests:
            tr = getattr(r, "trace", None)
            if tr is not None:
                # the attempt re-enters queue wait on this replica; the
                # failover hop itself is recorded by the fleet owner
                tr.to("queue")
                tr.hop("requeue", replica=self.replica_id)
        with self._cond:
            if self._closed:
                for r in requests:
                    r.resolve_exception(
                        RuntimeError("decode engine closed"))
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
                pass            # the tick thread

    def _admission_event(self, event):
        key = {"rejected": "rejected", "expired": "expired",
               "poisoned": "failed", "shed": "shed"}.get(event)
        if key is not None:
            with self._stats_lock:
                self._stats[key] += 1

    def stats(self):
        with self._stats_lock:
            s = dict(self._stats)
            occ_sum = self._occupancy_sum
        s["queue_depth"] = self.depth()
        s["active_slots"] = self.pool.used_slots()
        s["slots"] = self.slots
        s["avg_occupancy"] = (occ_sum / s["ticks"]) if s["ticks"] else 0.0
        s["executables"] = len(self._exec)
        s["traces"] = self._trace_count
        s.update({f"pool_{k}": v for k, v in self.pool.stats().items()
                  if isinstance(v, (int, float))})
        return s

    # -- the tick loop -----------------------------------------------------

    def _worker(self):
        while True:
            did_work = self.tick()
            if did_work:
                continue
            with self._cond:
                if not self._running:
                    if self._draining and (
                            self._queue or self.pool.used_slots()):
                        continue    # drain: keep ticking until empty
                    return
                if not self._queue and self.pool.used_slots() == 0:
                    self._cond.wait(0.05)

    def tick(self):
        """One engine step: admit into free slots (per the refill
        discipline), then advance every live sequence one token.
        Returns whether any work happened. Tests call this directly
        (``start=False``); the daemon loop drives it otherwise."""
        t0 = time.monotonic()
        with self._lock:
            self._tick_t0 = t0
        try:
            admitted = self._admit()
            stepped = (self._spec_once() if self.draft_model is not None
                       else self._decode_once())
        finally:
            with self._lock:
                self._tick_t0 = None
                self._last_progress = time.monotonic()
        return bool(admitted or stepped)

    # -- admission into slots ----------------------------------------------

    def _pop_next_locked(self, now):
        """Highest-priority (then FIFO) non-expired request, sweeping
        expired ones out as they surface. Caller holds the lock;
        expired requests are returned for resolution outside it."""
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

    def _admit(self):
        if self.refill == "drain" and self.pool.used_slots() != 0:
            return 0            # run-to-completion baseline: wait out
        admitted = 0            # the whole wave
        while self.pool.free_slots() > 0:
            now = time.monotonic()
            with self._cond:
                req, expired = self._pop_next_locked(now)
                depth = len(self._queue)
            for r in expired:
                self.admission.expire(r)
            metrics.record_queue_depth(depth)
            if req is None:
                break
            try:
                self._prefill_into_slot(req)
                admitted += 1
            except BaseException as e:   # noqa: BLE001 - to the future
                self._note_outcome(False, e)
                with self._stats_lock:
                    self._stats["failed"] += 1
                req.resolve_exception(e)
        return admitted

    def _ensure_capacity(self, needed_len):
        target = self.pool.capacity_for(needed_len)
        while self.pool.capacity < target:
            old = self.pool.capacity
            new = next_bucket(old + 1, self.pool.seq_buckets)
            fn = self._get_grow(old, new)
            self.pool.grow_to(new, lambda bufs, _o, _n: fn(bufs))
            if self.draft_pool is not None:
                # the two arenas share one page schedule; growing them
                # in lockstep keeps every spec executable single-cap
                dfn = self._get_grow(old, new, kind="dgrow")
                self.draft_pool.grow_to(new,
                                        lambda bufs, _o, _n: dfn(bufs))
            with self._stats_lock:
                self._stats["grows"] += 1
            # growth pad marker on the arena's shared lane — lines up
            # with the per-slot occupancy intervals in the Chrome export
            _monitor.trace.lane_instant(f"{self._lane}.pool",
                                        f"grow {old}->{new}",
                                        old_cap=old, new_cap=new)

    def _prefill_into_slot(self, req):
        """Prompt ingest: run the bucketed prefill executable, write the
        KV pages into a freed slot's arena rows, seat the sequence. The
        first generated token falls out of the prefill itself. A
        request carrying a ``preset`` payload (disaggregated handoff /
        KV-carrying migration) seats by segment import instead — no
        prefill executable runs."""
        import jax.numpy as jnp
        if getattr(req, "preset", None) is not None:
            return self._seat_preset(req)
        p = int(req.prompt.size)
        bucket = next_bucket(p, self.prompt_buckets)
        tr = req.trace
        if tr is not None:
            tr.to("prefill")
        # the arena must hold the prompt pages, the first decode write
        # (position p), and the full insert bucket
        self._ensure_capacity(max(p + 1, bucket))
        s = self.pool.alloc()
        if s is None:
            raise RuntimeError("no free slot after free_slots() > 0")
        pc_seat = time.perf_counter()
        try:
            if _faults.enabled():
                _faults.maybe_serving_fault(self.replica_id)
            t0 = time.monotonic()
            tokens = np.zeros((1, bucket), np.int32)
            tokens[0, :p] = req.prompt
            sp = req.sampling
            # generation index 0: the prefill's sampled token — the
            # same counter key a failover re-prefill will derive
            kv, first, _logits = self._get_prefill(bucket)(
                self.model.state, jnp.asarray(tokens),
                jnp.asarray([p], jnp.int32),
                jnp.asarray([sp.temperature], jnp.float32),
                jnp.asarray([sp.top_k], jnp.int32),
                jnp.asarray([sp.top_p], jnp.float32),
                jnp.asarray([sp.seed or 0], jnp.uint32),
                jnp.zeros((1,), jnp.int32))
            first = int(first[0])
            insert = self._get_insert(bucket, self.pool.capacity)
            with self.pool.arena_lock:
                self.pool.buffers = insert(self.pool.buffers, kv,
                                           jnp.int32(s))
            self.pool.note_length(s, p)
            if self.draft_pool is not None:
                dkv = self._get_draft_prefill(bucket)(
                    self._draft_state, jnp.asarray(tokens),
                    jnp.asarray([p], jnp.int32))
                dinsert = self._get_insert(
                    bucket, self.draft_pool.capacity, kind="dinsert")
                with self.draft_pool.arena_lock:
                    self.draft_pool.buffers = dinsert(
                        self.draft_pool.buffers, dkv, jnp.int32(s))
                self.draft_pool.note_length(s, p)
            ms = (time.monotonic() - t0) * 1e3
            metrics.record_prefill(p, ms, bucket)
            with self._stats_lock:
                self._stats["prefills"] += 1
                self._stats["prefill_tokens"] += p
        except BaseException:
            self.pool.free(s)
            raise
        self._note_outcome(True)
        # the TTFT moment: the prefill's last-token logits ARE the first
        # generated token (a failover re-prefill re-stamps it — honest)
        if tr is not None:
            tr.first_token()
        trc = _monitor.trace
        if trc.enabled():
            rid = tr.ctx.rid if tr is not None else None
            trc.lane_complete(f"{self._lane}.slot{s}", "prefill",
                              pc_seat, pc_seat + ms / 1e3,
                              rid=rid, tokens=p, bucket=bucket)
        done = (req.eos_token is not None and first == req.eos_token) \
            or req.max_new_tokens == 1
        if done:
            self.pool.free(s)
            if trc.enabled():
                trc.lane_complete(
                    f"{self._lane}.slot{s}",
                    f"req {rid}" if rid else "req", pc_seat,
                    rid=rid, tokens=1)
            self._complete(req, [first])
            return
        slot = self._slots[s]
        with self._lock:
            slot.req = req
            slot.length = p
            slot.tokens = [first]
            slot.last_token = first
            slot.t_seat = pc_seat

    def _seat_preset(self, req):
        """Seat a sequence whose KV history already exists as a host
        segment (``req.preset``): a disaggregated prefill→decode
        handoff (segment = the prompt's KV, tokens = [first]) or a
        KV-carrying drain migration (segment = prompt + generated KV,
        tokens = everything emitted so far). The segment lands through
        :meth:`KVCachePool.import_slot` on the pre-compiled insert
        executable for its pad bucket — zero fresh compiles — and the
        ``note_length`` ledger restores the generation index, so the
        continued stream is bit-identical to one that never moved."""
        import jax.numpy as jnp
        preset = req.preset
        seg = preset["segment"]
        pad = int(seg["pad"])
        L = int(seg["length"])
        toks = list(preset["tokens"])
        last = int(preset["last_token"])
        tr = req.trace
        self._ensure_capacity(max(L + 1, pad))
        s = self.pool.alloc()
        if s is None:
            raise RuntimeError("no free slot after free_slots() > 0")
        pc_seat = time.perf_counter()
        try:
            if _faults.enabled():
                _faults.maybe_serving_fault(self.replica_id)
            fn = self._get_insert(pad, self.pool.capacity)
            self.pool.import_slot(s, seg, insert_fn=fn)
            with self._stats_lock:
                self._stats["kv_imports"] = \
                    self._stats.get("kv_imports", 0) + 1
        except BaseException:
            self.pool.free(s)
            raise
        self._note_outcome(True)
        # the first token was stamped where it was produced (the
        # prefill pool / the original replica); entering "decode" here
        # closes the handoff (or requeue-wait) stage
        if tr is not None:
            tr.to("decode")
        trc = _monitor.trace
        rid = tr.ctx.rid if tr is not None else None
        if trc.enabled():
            trc.lane_complete(f"{self._lane}.slot{s}", "kv import",
                              pc_seat, time.perf_counter(),
                              rid=rid, tokens=L, pad=pad)
        done = (req.eos_token is not None and last == req.eos_token) \
            or len(toks) >= req.max_new_tokens
        if done:
            self.pool.free(s)
            self._complete(req, toks)
            return
        slot = self._slots[s]
        with self._lock:
            slot.req = req
            slot.length = L
            slot.tokens = toks
            slot.last_token = last
            slot.t_seat = pc_seat

    # -- the fused decode step ---------------------------------------------

    def _gather_batch(self, extra=1):
        """Snapshot the live lanes into the tick's host arrays: tokens /
        lengths / active plus the per-slot sampling knobs (the batch-
        shaped arrays that keep every request config on one executable)
        and each lane's generation index (= the counter the PRNG keys
        derive from). ``extra`` is the per-tick arena headroom (1 for
        plain decode, k+1 for a speculative verify). Caller must NOT
        hold the lock."""
        with self._lock:
            assigned = [(s, slot.req) for s, slot in enumerate(self._slots)
                        if slot.req is not None]
            if not assigned:
                return None
            tokens = np.zeros((self.slots,), np.int32)
            lengths = np.zeros((self.slots,), np.int32)
            active = np.zeros((self.slots,), bool)
            temps = np.zeros((self.slots,), np.float32)
            top_ks = np.zeros((self.slots,), np.int32)
            top_ps = np.ones((self.slots,), np.float32)
            seeds = np.zeros((self.slots,), np.uint32)
            positions = np.zeros((self.slots,), np.int32)
            max_needed = 0
            for s, req in assigned:
                slot = self._slots[s]
                sp = req.sampling
                tokens[s] = slot.last_token
                lengths[s] = slot.length
                active[s] = True
                temps[s] = sp.temperature
                top_ks[s] = sp.top_k
                top_ps[s] = sp.top_p
                seeds[s] = sp.seed or 0
                positions[s] = len(slot.tokens)
                max_needed = max(max_needed, slot.length + extra)
        return (assigned, tokens, lengths, active,
                (temps, top_ks, top_ps, seeds, positions), max_needed)

    def _decode_once(self):
        import jax.numpy as jnp
        batch = self._gather_batch(extra=1)
        if batch is None:
            return False
        assigned, tokens, lengths, active, samp, max_needed = batch
        self._ensure_capacity(max_needed)
        try:
            if _faults.enabled():
                _faults.maybe_serving_fault(self.replica_id)
            t0 = time.monotonic()
            fn = self._get_decode(self.pool.capacity)
            # the step DONATES the arena: dispatch and re-point
            # pool.buffers under the arena lock, so a drain thread's
            # export_slot never reads the consumed buffers
            args = [jnp.asarray(a)
                    for a in (tokens, lengths, active, *samp)]
            with self.pool.arena_lock:
                nxt, self.pool.buffers = fn(
                    self.model.state, self.pool.buffers, *args)
            nxt = np.asarray(nxt)
            step_ms = (time.monotonic() - t0) * 1e3
        except BaseException as e:   # noqa: BLE001 - fail the wave
            self._note_outcome(False, e)
            self._fail_active(assigned, e)
            return True
        self._note_outcome(True)
        finished = []
        with self._lock:
            n_active = 0
            for s, req in assigned:
                slot = self._slots[s]
                if slot.req is not req:
                    continue        # disowned / failed over mid-step
                n_active += 1
                tok = int(nxt[s])
                slot.length += 1
                slot.tokens.append(tok)
                slot.last_token = tok
                self.pool.note_length(s, slot.length)
                if (req.eos_token is not None and tok == req.eos_token) \
                        or len(slot.tokens) >= req.max_new_tokens:
                    finished.append((s, req, slot.tokens, slot.t_seat))
                    slot.req = None
                    slot.tokens = None
                    self.pool.free(s)
            occupancy = n_active / self.slots
        with self._stats_lock:
            self._stats["ticks"] += 1
            self._stats["tokens"] += n_active
            self._occupancy_sum += occupancy
        metrics.record_decode_tick(n_active, self.slots, n_active, step_ms)
        trc = _monitor.trace
        if trc.enabled() and finished:
            # slot occupancy intervals close when the slot frees — one
            # per finished sequence, on that slot's resource lane
            now_pc = time.perf_counter()
            for s, req, toks, t_seat in finished:
                rid = (req.trace.ctx.rid if req.trace is not None
                       else None)
                trc.lane_complete(f"{self._lane}.slot{s}",
                                  f"req {rid}" if rid else "req",
                                  t_seat, now_pc,
                                  rid=rid, tokens=len(toks))
        for _s, req, toks, _t in finished:
            self._complete(req, toks)
        return True

    def _spec_once(self):
        """One speculative tick: the draft proposes ``k`` tokens per
        live lane (one scan executable), the target verifies all k+1
        positions in one chunked call, and the host ledger settles each
        lane to its accepted prefix:

        * partial accept (``a < k``): emit ``d_1..d_a`` plus the
          residual resample — ``a + 1`` tokens;
        * full accept: emit exactly ``d_1..d_k`` and keep ``d_k`` as
          the next tick's input. **No bonus token** — emitting the
          target's k+1-th sample would leave the draft arena one
          entry behind the target's, and the catch-up write is a
          variable-shape call. Skipping it keeps both arenas in
          per-slot lockstep forever, for one token of upside.

        Both executables write optimistically; ``note_length`` then
        ``rollback`` trims each pool's ledger to the kept prefix
        (pure host bookkeeping — no device copies)."""
        import jax.numpy as jnp
        k = self.spec_k
        batch = self._gather_batch(extra=k + 1)
        if batch is None:
            return False
        assigned, tokens, lengths, active, samp, max_needed = batch
        # a lane within k of its admission-checked budget still verifies
        # a full k+1 chunk — the writes past max_len are dropped by the
        # scatter (OOB update semantics) and the ledger clamps below, so
        # the chunk shape (and the executable) never varies
        self._ensure_capacity(min(max_needed, self.pool.max_len))
        cap = self.pool.capacity
        try:
            if _faults.enabled():
                _faults.maybe_serving_fault(self.replica_id)
            t0 = time.monotonic()
            samp_dev = tuple(jnp.asarray(a) for a in samp)
            tok_dev = jnp.asarray(tokens)
            len_dev = jnp.asarray(lengths)
            act_dev = jnp.asarray(active)
            # both executables DONATE their arena: each dispatch and the
            # re-pointing of pool.buffers happen under that pool's arena
            # lock (see _decode_once), so the pools always point at live
            # buffers — also when a later call of this tick raises
            draft, verify = self._get_spec_draft(cap), \
                self._get_verify(cap)
            with self.draft_pool.arena_lock:
                ds, qs, self.draft_pool.buffers = draft(
                    self._draft_state, self.draft_pool.buffers,
                    tok_dev, len_dev, act_dev, *samp_dev)
            chunk = jnp.concatenate([tok_dev[:, None], ds], axis=1)
            with self.pool.arena_lock:
                a, resampled, self.pool.buffers = verify(
                    self.model.state, self.pool.buffers, chunk, len_dev,
                    act_dev, *samp_dev, ds, qs)
            a = np.asarray(a)
            resampled = np.asarray(resampled)
            ds_host = np.asarray(ds)
            step_ms = (time.monotonic() - t0) * 1e3
        except BaseException as e:   # noqa: BLE001 - fail the wave
            self._note_outcome(False, e)
            self._fail_active(assigned, e)
            return True
        self._note_outcome(True)
        finished = []
        emitted_total = 0
        accepted_total = 0
        with self._lock:
            n_active = 0
            for s, req in assigned:
                slot = self._slots[s]
                if slot.req is not req:
                    continue        # disowned / failed over mid-step
                n_active += 1
                L = int(lengths[s])
                ai = int(a[s])
                if ai >= k:
                    new_toks = [int(t) for t in ds_host[s]]
                else:
                    new_toks = [int(t) for t in ds_host[s, :ai]]
                    new_toks.append(int(resampled[s]))
                # EOS / budget truncate: everything past the stop token
                # is unemitted, so the live g-indexing never skews
                emitted = []
                done = False
                for t in new_toks:
                    emitted.append(t)
                    if (req.eos_token is not None
                            and t == req.eos_token) \
                            or len(slot.tokens) + len(emitted) \
                            >= req.max_new_tokens:
                        done = True
                        break
                e = len(emitted)
                # ledger settle: verify wrote target entries for chunk
                # tokens [last, d_1..d_k] at L..L+k; the draft scan
                # wrote [last, d_1..d_k-1] at L..L+k-1. Keep exactly
                # the new last_token's predecessors: L + e entries.
                # Clamp to capacity: a lane within k of max_len still
                # speculates a full chunk, and the tail writes past the
                # arena were dropped on-device (truncated here anyway).
                self.pool.note_length(s, min(L + k + 1, cap))
                self.pool.rollback(s, L + e)
                self.draft_pool.note_length(s, min(L + k, cap))
                if e < k:
                    self.draft_pool.rollback(s, L + e)
                slot.tokens.extend(emitted)
                slot.length = L + e
                slot.last_token = emitted[-1]
                if req.trace is not None:
                    req.trace.note_spec(k, ai)
                emitted_total += e
                accepted_total += ai
                if done:
                    finished.append((s, req, slot.tokens, slot.t_seat))
                    slot.req = None
                    slot.tokens = None
                    self.pool.free(s)
                    self.draft_pool.note_length(s, 0)
            occupancy = n_active / self.slots
        with self._stats_lock:
            self._stats["ticks"] += 1
            self._stats["tokens"] += emitted_total
            self._stats["draft_steps"] += k
            self._stats["verify_steps"] += 1
            self._stats["spec_proposed"] += k * n_active
            self._stats["spec_accepted"] += accepted_total
            self._occupancy_sum += occupancy
        metrics.record_decode_tick(n_active, self.slots, emitted_total,
                                   step_ms)
        metrics.record_spec_tick(k * n_active, accepted_total,
                                 emitted_total, k)
        trc = _monitor.trace
        if trc.enabled() and finished:
            now_pc = time.perf_counter()
            for s, req, toks, t_seat in finished:
                rid = (req.trace.ctx.rid if req.trace is not None
                       else None)
                trc.lane_complete(f"{self._lane}.slot{s}",
                                  f"req {rid}" if rid else "req",
                                  t_seat, now_pc,
                                  rid=rid, tokens=len(toks))
        for _s, req, toks, _t in finished:
            self._complete(req, toks)
        return True

    def _fail_active(self, assigned, exc):
        with self._lock:
            failed = []
            for s, req in assigned:
                slot = self._slots[s]
                if slot.req is not req:
                    continue
                failed.append((s, req, slot.t_seat))
                slot.req = None
                slot.tokens = None
                self.pool.free(s)
                if self.draft_pool is not None:
                    self.draft_pool.note_length(s, 0)
        with self._stats_lock:
            self._stats["failed"] += len(failed)
        trc = _monitor.trace
        if trc.enabled() and failed:
            now_pc = time.perf_counter()
            for s, _r, t_seat in failed:
                trc.lane_complete(f"{self._lane}.slot{s}", "req failed",
                                  t_seat, now_pc)
        for _s, r, _t in failed:
            r.resolve_exception(exc)

    def _complete(self, req, tokens):
        now = time.monotonic()
        latency_ms = req.age(now) * 1e3
        within = req.deadline is None or not req.deadline.expired(now)
        if req.trace is not None:
            # token count must land before resolve_result finalizes the
            # record — tpot derives from it
            req.trace.note_tokens(len(tokens))
        # account BEFORE resolving: the waiter wakes the instant
        # set_result lands, and a stats() read right after result()
        # must already see this completion
        metrics.record_completed(1, [latency_ms], within_sla=[within])
        with self._stats_lock:
            self._stats["completed"] += 1
        req.resolve_result(np.asarray(tokens, np.int32))


# ---------------------------------------------------------------------------
# fleet fan-out


def replicate_decode(model, devices=None):
    """One model view per device: the state pytree is ``device_put``
    onto each device; hyperparameters and the pure prefill/decode
    functions are shared (the decode analogue of ``multi.replicate``)."""
    import copy
    import jax
    devices = list(devices) if devices is not None else jax.local_devices()
    if not devices:
        raise ValueError("replicate_decode: no devices")
    out = []
    for d in devices:
        m = copy.copy(model)
        m.state = jax.device_put(model.state, d)
        m.device = d
        out.append(m)
    return out


class MultiDecodeEngine(MultiDeviceEngine):
    """Breaker-aware decode fan-out: one :class:`GenerateEngine` per
    device replica, behind the same supervision spine as fixed-shape
    serving — per-replica circuit breakers, hang failover (evicted
    sequences regenerate deterministically on the adopting replica),
    half-open probes, restart, and supervisor scaling (goodput floor
    plus the new ``tokens_floor``).

    Hedging defaults OFF for decode (``hedge_ms=0``): a decode request
    occupies a slot for its whole lifetime, so a hedge doubles slot
    pressure for the duration rather than shaving a straggler's tail —
    exactly the wrong trade under load. Pass ``hedge_ms`` explicitly to
    re-enable it for latency-critical, lightly-loaded fleets."""

    def __init__(self, model, devices=None, hedge_ms=0, **kwargs):
        super().__init__(model, devices=devices, hedge_ms=hedge_ms,
                         **kwargs)

    def _replicate(self, model, devices):
        return replicate_decode(model, devices)

    def _new_engine(self, model, index, on_outcome):
        return GenerateEngine(model, replica_id=index,
                              on_outcome=on_outcome,
                              **self._engine_kwargs)

    def submit(self, prompt, max_new_tokens=32, eos_token=None,
               deadline_ms=None, priority=None, trace=None,
               sampling=None, seed=None):
        rep = self._pick_replica()
        req = rep.engine.make_request(prompt,
                                      max_new_tokens=max_new_tokens,
                                      eos_token=eos_token,
                                      deadline_ms=deadline_ms,
                                      priority=priority, trace=trace,
                                      sampling=sampling, seed=seed)
        fut = rep.engine.submit_request(req)
        with self._hedge_lock:
            self._submitted += 1
        delay = self._hedge_delay_s
        if self._hedger is not None and delay and len(self._replicas) > 1:
            self._hedger.schedule(req, rep.index, delay)
        return fut

    def run(self, prompt, max_new_tokens=32, eos_token=None,
            deadline_ms=None, timeout=None, priority=None,
            sampling=None, seed=None):
        return self.submit(prompt, max_new_tokens=max_new_tokens,
                           eos_token=eos_token,
                           deadline_ms=deadline_ms,
                           priority=priority, sampling=sampling,
                           seed=seed).result(timeout)

    def _maybe_hedge(self, req, primary_index):
        """Decode hedge: re-prefill the same prompt on a second replica.
        The shadow carries the primary's resolved ``sampling`` (seed
        included), so greedy or sampled, both replicas derive the same
        counter keys and produce the same tokens; first resolution
        wins."""
        if req.future.done():
            return
        with self._hedge_lock:
            if self._hedged >= self.hedge_budget * self._submitted:
                return
            self._hedged += 1
        try:
            rep = self._pick_replica(exclude=(primary_index,))
        except Exception:
            with self._hedge_lock:
                self._hedged -= 1
            return
        ptr = req.trace
        shadow = DecodeRequest(req.prompt, req.max_new_tokens,
                               eos_token=req.eos_token,
                               deadline=req.deadline,
                               priority=req.priority,
                               sampling=req.sampling,
                               # the shadow rides the SAME context as a
                               # hedge attempt: whichever resolution wins
                               # the shared done-latch emits the record
                               trace=(None if ptr is None else
                                      ptr.ctx.attempt("hedge",
                                                      rep.index)))
        if ptr is not None:
            ptr.hop("hedge", replica=rep.index)
        metrics.record_hedge(replica=rep.index)

        def _on_shadow_done(sf, _req=req, _idx=rep.index):
            if sf.cancelled() or sf.exception() is not None:
                return
            try:
                _req.future.set_result(sf.result())
            except concurrent.futures.InvalidStateError:
                return
            with self._hedge_lock:
                self._hedge_wins += 1
            metrics.record_hedge_win(replica=_idx)

        shadow.future.add_done_callback(_on_shadow_done)
        try:
            rep.engine.submit_request(shadow)
        except Exception:
            with self._hedge_lock:
                self._hedged -= 1


# ---------------------------------------------------------------------------
# the reference decode model


class DemoLM:
    """A small causal-LM implementation of the decode-model contract:
    tied-embedding transformer (RMSNorm, per-layer attention + MLP),
    prefill through the flash-attention op (sdpa fallback off-TPU),
    decode as a single-token attention over the KV arena. Fixed random
    weights — it generates structured gibberish deterministically,
    which is exactly what throughput and parity tests need."""

    def __init__(self, vocab=64, dim=32, heads=2, layers=2, max_len=512,
                 seed=0):
        import jax
        import jax.numpy as jnp
        if dim % heads:
            raise ValueError(f"dim {dim} not divisible by heads {heads}")
        self.vocab = int(vocab)
        self.dim = int(dim)
        self.heads = int(heads)
        self.head_dim = self.dim // self.heads
        self.layers = int(layers)
        self.max_len = int(max_len)
        keys = jax.random.split(jax.random.PRNGKey(seed),
                                2 + 6 * self.layers)
        scale = 1.0 / np.sqrt(self.dim)
        state = {"embed": jax.random.normal(
            keys[0], (self.vocab, self.dim), jnp.float32) * scale}
        # sinusoidal positions: deterministic, length-extensible, and
        # identical between prefill and decode by construction
        pos = np.arange(self.max_len)[:, None]
        div = np.exp(np.arange(0, self.dim, 2)
                     * (-np.log(10000.0) / self.dim))
        table = np.zeros((self.max_len, self.dim), np.float32)
        table[:, 0::2] = np.sin(pos * div)
        table[:, 1::2] = np.cos(pos * div)
        state["pos"] = jnp.asarray(table)
        for layer in range(self.layers):
            k = keys[2 + 6 * layer: 8 + 6 * layer]
            state[f"wq{layer}"] = jax.random.normal(
                k[0], (self.dim, self.dim), jnp.float32) * scale
            state[f"wk{layer}"] = jax.random.normal(
                k[1], (self.dim, self.dim), jnp.float32) * scale
            state[f"wv{layer}"] = jax.random.normal(
                k[2], (self.dim, self.dim), jnp.float32) * scale
            state[f"wo{layer}"] = jax.random.normal(
                k[3], (self.dim, self.dim), jnp.float32) * scale
            state[f"w1{layer}"] = jax.random.normal(
                k[4], (self.dim, 2 * self.dim), jnp.float32) * scale
            state[f"w2{layer}"] = jax.random.normal(
                k[5], (2 * self.dim, self.dim), jnp.float32) * scale
        self.state = state
        self.device = None

    def kv_spec(self):
        tail = (self.heads, self.head_dim)
        spec = {}
        for layer in range(self.layers):
            spec[f"k{layer}"] = (tail, "float32")
            spec[f"v{layer}"] = (tail, "float32")
        return spec

    @staticmethod
    def _norm(x):
        import jax.numpy as jnp
        return x * jnp.reciprocal(
            jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                     + 1e-6))

    def prefill_fn(self, state, tokens, lengths):
        """Full-prompt forward: (B, L) -> KV chunks + last-token logits.
        Causal attention makes end-padding harmless — every real
        position only sees real positions."""
        import jax.numpy as jnp
        from ..ops.pallas.flash_attention import flash_attention
        b, seq = tokens.shape
        h, hd = self.heads, self.head_dim
        x = state["embed"][tokens] + state["pos"][:seq][None]
        kv = {}
        for layer in range(self.layers):
            hidden = self._norm(x)
            q = (hidden @ state[f"wq{layer}"]).reshape(b, seq, h, hd)
            k = (hidden @ state[f"wk{layer}"]).reshape(b, seq, h, hd)
            v = (hidden @ state[f"wv{layer}"]).reshape(b, seq, h, hd)
            kv[f"k{layer}"] = k
            kv[f"v{layer}"] = v
            out = flash_attention(jnp.transpose(q, (0, 2, 1, 3)),
                                  jnp.transpose(k, (0, 2, 1, 3)),
                                  jnp.transpose(v, (0, 2, 1, 3)),
                                  causal=True)
            out = getattr(out, "data", out)     # dispatch may wrap Tensor
            out = jnp.transpose(out, (0, 2, 1, 3)).reshape(b, seq,
                                                           self.dim)
            x = x + out @ state[f"wo{layer}"]
            hidden = self._norm(x)
            x = x + jnp.maximum(
                hidden @ state[f"w1{layer}"], 0.0) @ state[f"w2{layer}"]
        logits = self._norm(x) @ state["embed"].T
        last = jnp.take_along_axis(
            logits, (lengths - 1)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        return kv, last

    def decode_fn(self, state, tokens, kv, lengths):
        """One token per slot against the KV arena: attend over the
        resident history (masked by live length) plus the incoming
        token's own K/V — the same math as prefill position
        ``lengths`` — and emit that token's cache entry."""
        import jax.numpy as jnp
        s = tokens.shape[0]
        h, hd = self.heads, self.head_dim
        cap = next(iter(kv.values())).shape[1]
        inv = 1.0 / np.sqrt(hd)
        x = state["embed"][tokens] + state["pos"][lengths]
        entry = {}
        hist_mask = (jnp.arange(cap)[None, None, :]
                     < lengths[:, None, None])
        for layer in range(self.layers):
            hidden = self._norm(x)
            q = (hidden @ state[f"wq{layer}"]).reshape(s, h, hd)
            k_new = (hidden @ state[f"wk{layer}"]).reshape(s, h, hd)
            v_new = (hidden @ state[f"wv{layer}"]).reshape(s, h, hd)
            entry[f"k{layer}"] = k_new
            entry[f"v{layer}"] = v_new
            scores_h = jnp.einsum("shd,schd->shc", q,
                                  kv[f"k{layer}"]) * inv
            scores_h = jnp.where(hist_mask, scores_h, -1e9)
            score_s = jnp.sum(q * k_new, axis=-1,
                              keepdims=True) * inv
            scores = jnp.concatenate([scores_h, score_s], axis=-1)
            probs = jnp.exp(scores - jnp.max(scores, axis=-1,
                                             keepdims=True))
            probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
            out = jnp.einsum("shc,schd->shd", probs[..., :cap],
                             kv[f"v{layer}"]) \
                + probs[..., cap:] * v_new
            x = x + out.reshape(s, self.dim) @ state[f"wo{layer}"]
            hidden = self._norm(x)
            x = x + jnp.maximum(
                hidden @ state[f"w1{layer}"], 0.0) @ state[f"w2{layer}"]
        logits = self._norm(x) @ state["embed"].T
        return logits, entry

    def verify_fn(self, state, tokens, kv, lengths):
        """Chunked decode — ``decode_fn`` generalized to a ``(S, C)``
        chunk for speculative verify. Chunk position ``i`` sits at
        arena position ``lengths + i``: it attends over the resident
        history (masked by live length) plus chunk positions ``<= i``,
        and all C cache entries come back for the engine's optimistic
        write. The C == 1 case computes exactly what ``decode_fn``
        does (masked scores are exact zeros after softmax, so the
        extra padded lanes never perturb the sums) — that identity is
        the greedy-parity gate in scripts/spec_smoke.py."""
        import jax.numpy as jnp
        s, c = tokens.shape
        h, hd = self.heads, self.head_dim
        cap = next(iter(kv.values())).shape[1]
        inv = 1.0 / np.sqrt(hd)
        positions = lengths[:, None] + jnp.arange(c)[None, :]
        x = state["embed"][tokens] + state["pos"][positions]
        entry = {}
        hist_mask = (jnp.arange(cap)[None, None, None, :]
                     < lengths[:, None, None, None])      # [S,1,1,cap]
        self_mask = (jnp.arange(c)[None, :]
                     <= jnp.arange(c)[:, None])[None, :, None, :]
        for layer in range(self.layers):
            hidden = self._norm(x)
            q = (hidden @ state[f"wq{layer}"]).reshape(s, c, h, hd)
            k_new = (hidden @ state[f"wk{layer}"]).reshape(s, c, h, hd)
            v_new = (hidden @ state[f"wv{layer}"]).reshape(s, c, h, hd)
            entry[f"k{layer}"] = k_new
            entry[f"v{layer}"] = v_new
            scores_h = jnp.einsum("schd,sChd->schC", q,
                                  kv[f"k{layer}"]) * inv
            scores_h = jnp.where(hist_mask, scores_h, -1e9)
            scores_c = jnp.einsum("schd,sChd->schC", q, k_new) * inv
            scores_c = jnp.where(self_mask, scores_c, -1e9)
            scores = jnp.concatenate([scores_h, scores_c], axis=-1)
            probs = jnp.exp(scores - jnp.max(scores, axis=-1,
                                             keepdims=True))
            probs = probs / jnp.sum(probs, axis=-1, keepdims=True)
            out = jnp.einsum("schC,sChd->schd", probs[..., :cap],
                             kv[f"v{layer}"]) \
                + jnp.einsum("schC,sChd->schd", probs[..., cap:], v_new)
            x = x + out.reshape(s, c, self.dim) @ state[f"wo{layer}"]
            hidden = self._norm(x)
            x = x + jnp.maximum(
                hidden @ state[f"w1{layer}"], 0.0) @ state[f"w2{layer}"]
        logits = self._norm(x) @ state["embed"].T
        return logits, entry


def demo_model(vocab=64, dim=32, heads=2, layers=2, max_len=512, seed=0):
    """The reference decode model for docs, tests, the loadgen, and the
    smoke scripts."""
    return DemoLM(vocab=vocab, dim=dim, heads=heads, layers=layers,
                  max_len=max_len, seed=seed)


def demo_spec_pair(vocab=64, dim=32, heads=2, draft_layers=1,
                   extra_layers=1, max_len=512, seed=0, distill=0.15):
    """A (target, draft) :class:`DemoLM` pair built for a high accept
    rate — the shape a distilled draft gives you in production:

    * the target is a ``draft_layers + extra_layers`` model whose
      *refinement* layers' weights are scaled by ``distill`` — each
      extra layer's residual contribution lands at roughly
      ``distill**2`` (q·k and w1·w2 both carry two damped factors), so
      the target's distribution is a small perturbation of its prefix;
    * the draft IS that prefix: it shares the embedding / position /
      first-``draft_layers`` weight **arrays** with the target (a
      rebuild from the same seed would re-split the PRNG differently),
      so the pair costs one model's memory plus the extra layers.

    Smaller ``distill`` → higher accept rate → more emitted tokens per
    verify step; the loadgen A/B and scripts/spec_smoke.py use this
    pair to demonstrate the speculative speedup honestly (same target
    math on both sides of the A/B)."""
    import copy
    target = DemoLM(vocab=vocab, dim=dim, heads=heads,
                    layers=draft_layers + extra_layers,
                    max_len=max_len, seed=seed)
    eps = float(distill)
    state = dict(target.state)
    for layer in range(draft_layers, target.layers):
        for w in ("wq", "wk", "wv", "wo", "w1", "w2"):
            state[f"{w}{layer}"] = state[f"{w}{layer}"] * eps
    target.state = state
    draft = copy.copy(target)
    draft.layers = int(draft_layers)
    draft.state = {k: v for k, v in state.items()
                   if k in ("embed", "pos")
                   or int(k[2:]) < draft.layers}
    return target, draft
