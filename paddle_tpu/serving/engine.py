"""paddle_tpu.serving.engine — a Predictor as an online endpoint.

``ServingEngine`` composes the pieces: the batcher decides *when* a
coalesced group flushes (``max_batch`` rows or ``timeout_ms``,
whichever first); the engine decides *how* — concatenate the group's
inputs along the batch axis, pad to the next ``io.bucketing`` bucket
(repeat-mode, so pad rows stay in-distribution), run the wrapped
``Predictor`` on a pre-compiled bucket shape, slice every request's
rows back out, and resolve its future with host numpy outputs
(bit-identical to ``Predictor.run`` on the lone request at the same
padded shape — which requests share the flush never shows; against
an UNPADDED run, XLA may pick another kernel for the other shape and
the last ulp can differ).

:meth:`warmup` AOT-compiles every (bucket, signature) pair up front via
``Predictor.warmup`` — ``lower().compile()`` over ShapeDtypeStructs,
the ``Executor.warmup`` discipline — so steady-state traffic performs
**zero** compiles (asserted by ``scripts/serving_smoke.py`` via the
``serving.compiles`` counter).

Failure semantics ride ``admission.py``: transient batch failures are
retried under the ``RetryPolicy``; terminal ones re-run the group
request-by-request so a poisoned request fails only its own future.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from .. import monitor as _monitor
from ..io.bucketing import next_bucket, pad_to_bucket, split_rows, unpad
from ..resilience import faults as _faults
from ..tensor import Tensor
from .admission import AdmissionController, resolve_priority
from .batcher import DynamicBatcher, Request
from . import metrics
from . import reqtrace

# host-side feed canonicalization, matching Executor's (and jax's
# x64-disabled) convention so a float64 submit and the float32 warmup
# signature share one executable
_CANON = {np.dtype("float64"): np.dtype("float32"),
          np.dtype("int64"): np.dtype("int32"),
          np.dtype("uint64"): np.dtype("uint32"),
          np.dtype("complex128"): np.dtype("complex64")}


def _as_host_array(x):
    if isinstance(x, Tensor):
        x = x.data
    a = np.asarray(x)
    tgt = _CANON.get(a.dtype)
    return a.astype(tgt) if tgt is not None else a


class ServingEngine:
    """Dynamic-batching online inference over one ``Predictor``.

    Parameters
    ----------
    predictor : inference.Predictor (already precision-converted)
    buckets : batch-size bucket set; default powers of two up to
        ``max_batch``. Always normalized to include ``max_batch`` and
        exclude anything above it, so every flush lands on a warmable
        shape.
    max_batch : row cap per coalesced batch (also the largest single
        request accepted).
    timeout_ms : max time the oldest queued request waits before a
        partial batch flushes.
    queue_depth : admission bound — submits past it fast-reject with
        ``QueueFullError``.
    deadline_ms : default per-request SLA (None = no deadline unless
        the submit carries one).
    retry_policy : ``resilience.retry.RetryPolicy`` classifying batch
        failures.
    start : launch the drain thread now (False = tests drive it
        manually via ``.start()``).
    metrics_port : also start ``monitor.serve(port=metrics_port)`` —
        the live /metrics + /healthz + /snapshot endpoint (0 picks an
        ephemeral port; ``monitor.export.port()`` tells you which).
        The server is process-global and outlives this engine;
        ``monitor.disable()`` tears it down.
    """

    def __init__(self, predictor, buckets=None, max_batch=32,
                 timeout_ms=5.0, queue_depth=256, deadline_ms=None,
                 retry_policy=None, start=True, metrics_port=None,
                 replica_id=None, on_outcome=None, shed=True,
                 slo_goodput_floor=0.90, seq_buckets=None):
        self.predictor = predictor
        # sequence-length buckets for ragged prompts: inputs with a
        # second (sequence) axis are padded up to the next bucket
        # BEFORE the coalescing signature is computed, so prompts of
        # length 7/12/15 all group as one bucket-16 signature instead
        # of fragmenting into per-length single-request batches. The
        # model must treat pad positions as inert (causal attention or
        # an explicit length mask — see docs/serving.md); per-request
        # outputs are sliced back to the real length at scatter.
        self.seq_buckets = (tuple(sorted({int(b) for b in seq_buckets}))
                            if seq_buckets else None)
        # identity inside a MultiDeviceEngine fleet (fault targeting,
        # breaker gauges); None for a standalone engine
        self.replica_id = replica_id
        # served weights version: bumped by the fleet's rolling
        # hot-swap and stamped into every request's reqtrace record
        self.weights_version = 0
        # breaker feedback: called with (ok: bool, exc|None) after each
        # batch execution attempt settles
        self.on_outcome = on_outcome
        self.max_batch = int(max_batch)
        if self.max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if buckets:
            bs = {int(b) for b in buckets if int(b) <= self.max_batch}
        else:
            bs, b = set(), 1
            while b < self.max_batch:
                bs.add(b)
                b <<= 1
        bs.add(self.max_batch)
        self.buckets = sorted(bs)
        self.admission = AdmissionController(
            max_queue_depth=queue_depth,
            default_deadline_ms=deadline_ms,
            retry_policy=retry_policy, shed=shed,
            slo_goodput_floor=slo_goodput_floor)
        self.admission.on_event = self._admission_event
        self._batcher = DynamicBatcher(
            self._process, self.admission,
            max_batch=self.max_batch, timeout_ms=timeout_ms)
        self._stats_lock = threading.Lock()
        self._stats = {"submitted": 0, "completed": 0, "failed": 0,
                       "rejected": 0, "expired": 0, "shed": 0,
                       "batches": 0, "coalesced_rows": 0,
                       "padded_rows": 0, "compiles": 0, "retries": 0,
                       "isolated": 0}
        # a 1-row copy of the first submit's inputs: the supervisor's
        # half-open probe replays it as budgeted test traffic
        self._probe_template = None
        self._last_ok_t = time.monotonic()
        # live-telemetry wiring: the sampler republishes this engine's
        # queue depth each tick (a gauge set only at enqueue/dequeue
        # edges goes stale the moment traffic stops), weakly so an
        # un-closed engine can still be collected
        import weakref
        from ..monitor import sampler as _sampler
        ref = weakref.ref(self)

        def _depth_series():
            eng = ref()
            if eng is None:
                return None  # provider dies with the engine
            return {"serving.queue_depth": eng._batcher.depth()}

        self._sampler_key = _sampler.register_provider(
            f"serving-engine-{id(self)}", _depth_series)
        if metrics_port is not None:
            # serve-while-serving: expose /metrics + /healthz for the
            # lifetime of the process (monitor.disable() tears it down)
            _monitor.serve(port=metrics_port)
        if start:
            self.start()

    # -- client surface ---------------------------------------------------

    def make_request(self, inputs, deadline_ms=None, priority=None,
                     trace=None):
        """Validate + canonicalize one submit's inputs into a
        ``Request`` (not yet enqueued — ``MultiDeviceEngine`` builds
        the request once, then picks which replica's
        :meth:`submit_request` gets it). Raises ``ValueError`` on
        malformed inputs. ``trace=`` carries an existing
        ``reqtrace.RequestTrace`` across a shed-then-retry resubmit so
        the retry stays the SAME logical request (one terminal record,
        backoff blamed as ``shed_retry_ms``)."""
        if not inputs:
            raise ValueError("submit() needs at least one input array")
        arrays = tuple(_as_host_array(x) for x in inputs)
        if any(a.ndim < 1 for a in arrays):
            raise ValueError(
                "serving inputs need a leading batch dimension")
        n = arrays[0].shape[0]
        if any(a.shape[0] != n for a in arrays):
            raise ValueError(
                f"inconsistent leading dims: "
                f"{[a.shape[0] for a in arrays]}")
        if n < 1:
            raise ValueError("empty request (0 rows)")
        if n > self.max_batch:
            raise ValueError(
                f"request of {n} rows exceeds max_batch={self.max_batch}"
                f" — split it client-side")
        from ..resilience.deadline import Deadline
        deadline = (Deadline.after_ms(deadline_ms)
                    if deadline_ms is not None else None)
        seq_real = seq_padded = None
        if self.seq_buckets:
            # pad the sequence axis to its bucket BEFORE the signature:
            # this is what lets ragged prompts coalesce into one
            # executable signature (repeat-mode pad — rows stay
            # in-distribution, causal/masked models ignore them)
            padded, pads = [], set()
            for a in arrays:
                if a.ndim >= 2 and a.shape[1] > 0:
                    seq_n = a.shape[1]
                    target = next_bucket(seq_n, self.seq_buckets)
                    if target != seq_n:
                        a = pad_to_bucket(a, target, axis=1)
                    pads.add((seq_n, target))
                padded.append(a)
            arrays = tuple(padded)
            if len(pads) == 1:
                (seq_real, seq_padded), = pads
        sig = tuple((a.shape[1:], str(a.dtype)) for a in arrays)
        prio = resolve_priority(priority)
        return Request(arrays, n, sig, deadline=deadline,
                       priority=prio,
                       seq_real=seq_real, seq_padded=seq_padded,
                       trace=reqtrace.attach(trace, kind="serve",
                                             priority=prio,
                                             replica=self.replica_id,
                                             version=self.weights_version))

    def submit_request(self, req):
        """Enqueue an already-built ``Request``; returns its future.
        Raises ``ShedError`` / ``QueueFullError`` from admission."""
        if self._probe_template is None:
            self._probe_template = tuple(a[:1].copy() for a in req.inputs)
        with _monitor.trace.span("serving.enqueue", rows=req.n):
            fut = self._batcher.submit(req)
            if req.trace is not None:
                req.trace.hop("enqueue", replica=self.replica_id)
                reqtrace.flow_mark(req.trace)
        with self._stats_lock:
            self._stats["submitted"] += 1
        return fut

    def submit(self, *inputs, deadline_ms=None, priority=None,
               trace=None):
        """Enqueue one request (each input shaped ``(n, ...)``, all with
        the same leading ``n <= max_batch``); returns a
        ``concurrent.futures.Future`` resolving to what
        ``Predictor.run`` on the same inputs returns. ``priority`` is
        'high'/'normal'/'low' (default 'normal') — under overload the
        admission ladder sheds low classes first. Raises ``ShedError``
        / ``QueueFullError`` under overload, ``ValueError`` on
        malformed inputs. A caller retrying after a shed passes the
        shed request's ``trace`` back so the retry is attributed to the
        same logical request."""
        return self.submit_request(self.make_request(
            inputs, deadline_ms=deadline_ms, priority=priority,
            trace=trace))

    def run(self, *inputs, deadline_ms=None, timeout=None, priority=None):
        """Blocking submit: enqueue, wait, return the outputs (or raise
        what the request's future raised)."""
        return self.submit(*inputs, deadline_ms=deadline_ms,
                           priority=priority).result(timeout)

    def warmup(self, *signatures):
        """AOT-compile every (bucket, signature) pair. Each signature is
        a list of per-input ``(example_shape, dtype)`` pairs — the shape
        WITHOUT the batch dim, e.g. ``[((16,), "float32")]`` for a
        single ``(n, 16)`` float input. Returns the number of
        executables compiled."""
        before = len(self.predictor._compiled)
        with _monitor.trace.span("serving.warmup",
                                 buckets=len(self.buckets)):
            for sig in signatures:
                norm = []
                for item in sig:
                    if hasattr(item, "shape") and hasattr(item, "dtype"):
                        norm.append((tuple(item.shape), item.dtype))
                    else:
                        shape, dtype = item
                        norm.append((tuple(shape), dtype))
                for b in self.buckets:
                    self.predictor.warmup(
                        [((b,) + shape, dtype) for shape, dtype in norm])
                if self._probe_template is None and norm:
                    # a freshly (re)started replica has served nothing:
                    # synthesize probe input from the warmup signature so
                    # the supervisor can still test it back to health
                    self._probe_template = tuple(
                        np.zeros((1,) + shape, dtype=dtype)
                        for shape, dtype in norm)
        fresh = len(self.predictor._compiled) - before
        if fresh:
            metrics.record_compiles(fresh)
            with self._stats_lock:
                self._stats["compiles"] += fresh
        return fresh

    def start(self):
        self._batcher.start()

    def close(self, drain=True, timeout=None):
        self._batcher.close(drain=drain, timeout=timeout)
        from ..monitor import sampler as _sampler
        _sampler.unregister_provider(self._sampler_key)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()

    # -- supervision surface ----------------------------------------------

    def heartbeat(self, now=None):
        """Liveness signals for the ``ServingSupervisor``: queue depth,
        whether a batch is currently dispatched and for how long, time
        since the drain thread last made progress, and time since the
        last successful batch."""
        now = time.monotonic() if now is None else now
        age = self._batcher.inflight_age(now)
        return {
            "queue_depth": self._batcher.depth(),
            "inflight_age_s": age,
            "inflight_token": self._batcher.inflight_token(),
            "last_progress_age_s": self._batcher.last_progress_age(now),
            "last_ok_age_s": now - self._last_ok_t,
            # in-flight request count — what a drain waits to hit zero
            "active": 0 if age is None else 1,
        }

    def probe(self, timeout_s=1.0):
        """Half-open test traffic: replay a 1-row copy of real input
        through the full assemble→execute path on a side thread (the
        drain thread may be stuck — that's exactly what we're probing)
        and report whether it finished in time. No future, no queue:
        the probe must not compete with, or be blocked by, real work."""
        template = self._probe_template
        if template is None:
            return None     # nothing served yet — nothing to replay
        done = threading.Event()
        err = []

        def _go():
            try:
                sig = tuple((a.shape[1:], str(a.dtype)) for a in template)
                req = Request(tuple(a.copy() for a in template), 1, sig)
                arrays, _real, _bucket = self._assemble([req])
                self._run_batch(arrays)
            except BaseException as e:  # noqa: BLE001 - probe verdict
                err.append(e)
            finally:
                done.set()

        threading.Thread(target=_go, daemon=True,
                         name="paddle_tpu-serving-probe").start()
        ok = done.wait(timeout_s) and not err
        if ok:
            self._last_ok_t = time.monotonic()
        return bool(ok)

    def steal_pending(self):
        """Failover: hand every queued request to the caller."""
        return self._batcher.steal_pending()

    def disown_inflight(self):
        """Failover: hand over the currently dispatched group."""
        return self._batcher.disown_inflight()

    def requeue(self, requests):
        """Failover: accept already-admitted requests at queue front."""
        for r in requests:
            tr = getattr(r, "trace", None)
            if tr is not None:
                # back to queue wait on the adopting replica; the
                # failover hop itself is recorded by the fleet owner
                tr.to("queue")
                tr.hop("requeue", replica=self.replica_id)
        self._batcher.requeue(requests)

    def _note_outcome(self, ok, exc=None):
        if ok:
            self._last_ok_t = time.monotonic()
        cb = self.on_outcome
        if cb is not None:
            try:
                cb(ok, exc)
            except Exception:   # noqa: BLE001 - observer must not kill
                pass            # the drain thread

    def _admission_event(self, event):
        key = {"rejected": "rejected", "expired": "expired",
               "poisoned": "failed", "shed": "shed"}.get(event)
        if key is not None:
            with self._stats_lock:
                self._stats[key] += 1

    def stats(self):
        """Engine-local accounting (independent of the monitor): every
        submitted request is completed, failed, expired or still
        queued — the smoke gate's zero-lost-futures check."""
        with self._stats_lock:
            s = dict(self._stats)
        s["queue_depth"] = self._batcher.depth()
        s["buckets"] = list(self.buckets)
        return s

    # -- batch execution (drain thread) -----------------------------------

    def _process(self, requests):
        """One coalesced same-signature group: assemble → execute (with
        retry/isolation) → scatter."""
        with self._stats_lock:
            self._stats["batches"] += 1
        with _monitor.trace.span("serving.batch_assemble",
                                 requests=len(requests)):
            # queue time ends here: the drain thread owns the group now
            reqtrace.transition(requests, "assemble", flow=True)
            arrays, real_n, bucket = self._assemble(requests)
        metrics.record_batch(real_n, bucket, len(requests))
        with self._stats_lock:
            self._stats["coalesced_rows"] += real_n
            self._stats["padded_rows"] += bucket - real_n
        outs = self._execute_with_recovery(requests, arrays)
        if outs is None:
            return      # isolation path resolved every future already
        with _monitor.trace.span("serving.scatter",
                                 requests=len(requests)):
            self._scatter(requests, outs)

    def _assemble(self, requests):
        """Concatenate the group's inputs along the batch axis and pad
        to the next bucket (repeat-mode: pad rows stay in-distribution;
        their outputs are dropped at scatter — the ``batch_mask``
        contract from io.bucketing)."""
        real_n = sum(r.n for r in requests)
        bucket = next_bucket(real_n, self.buckets)
        arrays = []
        for i in range(len(requests[0].inputs)):
            parts = [r.inputs[i] for r in requests]
            a = parts[0] if len(parts) == 1 else np.concatenate(parts,
                                                                axis=0)
            arrays.append(pad_to_bucket(a, bucket))
        return arrays, real_n, bucket

    def _run_batch(self, arrays):
        """Execute one bucket-shaped batch; returns a tuple of device
        outputs plus whether the model is multi-output. Counts fresh
        executables into ``serving.compiles`` (zero in steady state)."""
        before = len(self.predictor._compiled)
        if _faults.enabled():
            # the chaos gate's injection site: replica_error raises,
            # replica_hang/replica_slow stall right where a stuck
            # device runtime would
            _faults.maybe_serving_fault(self.replica_id)
        with _monitor.trace.span("serving.execute",
                                 rows=int(arrays[0].shape[0])):
            out = self.predictor.run_device(*arrays)
        fresh = len(self.predictor._compiled) - before
        if fresh:
            metrics.record_compiles(fresh)
            with self._stats_lock:
                self._stats["compiles"] += fresh
        multi = isinstance(out, (tuple, list))
        return (tuple(out) if multi else (out,)), multi

    def _execute_with_recovery(self, requests, arrays):
        """Transient failures retry the whole batch under the admission
        policy; terminal (or exhausted) ones fall to per-request
        isolation — one poisoned request fails its own future only."""
        policy = self.admission.retry_policy
        attempt = 0
        while True:
            try:
                reqtrace.transition(requests, "execute")
                out = self._run_batch(arrays)
                self._note_outcome(True)
                return out
            except BaseException as e:  # noqa: BLE001 - triaged below
                self._note_outcome(False, e)
                if policy.is_transient(e) \
                        and attempt + 1 < policy.max_attempts:
                    metrics.record_retry(where="serving.execute")
                    with self._stats_lock:
                        self._stats["retries"] += 1
                    with _monitor.trace.span("serving.retry_backoff",
                                             attempt=attempt + 1):
                        reqtrace.transition(requests, "retry_backoff")
                        time.sleep(policy.delay(attempt))
                    attempt += 1
                    continue
                with self._stats_lock:
                    self._stats["isolated"] += len(requests)
                self.admission.isolate(requests, self._run_one, e)
                return None

    def _run_one(self, request):
        """Isolation path: execute ONE request alone (still bucket-
        padded, so no fresh shapes are minted) and resolve its future.
        Raises to the caller (admission.isolate) if this request is the
        poison."""
        reqtrace.transition([request], "execute")
        arrays, _real, _bucket = self._assemble([request])
        outs, multi = self._run_batch(arrays)
        self._scatter([request], (outs, multi))

    def _scatter(self, requests, outs_multi):
        """Slice each request's rows back out, device→host once for the
        whole batch, resolve futures, record latency."""
        outs, multi = outs_multi
        reqtrace.transition(requests, "scatter", flow=True)
        import jax
        host = [np.asarray(jax.device_get(o)) for o in outs]
        bucket = None
        for a in host:
            if getattr(a, "ndim", 0) >= 1:
                bucket = a.shape[0]
                break
        sizes = [r.n for r in requests]
        per_out_chunks = []
        for a in host:
            if getattr(a, "ndim", 0) >= 1 and a.shape[0] == bucket:
                per_out_chunks.append(split_rows(a, sizes))
            else:
                # no batch dim (a scalar reduction): every request gets
                # the whole thing — documented in docs/serving.md
                per_out_chunks.append([a] * len(requests))
        now = time.monotonic()
        latencies, within = [], []
        for j, r in enumerate(requests):
            vals = [chunks[j] for chunks in per_out_chunks]
            if r.seq_padded is not None and r.seq_real != r.seq_padded:
                # bucket-padded sequence axis: slice outputs that kept
                # the padded length back to the request's real length
                vals = [unpad(v, r.seq_real, axis=1)
                        if getattr(v, "ndim", 0) >= 2
                        and v.shape[1] == r.seq_padded else v
                        for v in vals]
            r.resolve_result(list(vals) if multi else vals[0])
            latencies.append(r.age(now) * 1e3)
            # the slo.* goodput numerator: resolved before its SLA ran
            # out (no deadline = always within)
            within.append(r.deadline is None
                          or not r.deadline.expired(now))
        metrics.record_completed(len(requests), latencies,
                                 within_sla=within)
        with self._stats_lock:
            self._stats["completed"] += len(requests)
