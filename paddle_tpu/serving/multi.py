"""paddle_tpu.serving.multi — self-healing data-parallel replica fan-out.

A multi-chip inference host serves best as N independent replicas, not
one sharded model: each device holds a full copy of the state
(``jax.device_put`` — the serving analogue of data parallelism), runs
its own dynamic batcher, and the front door spreads request streams
across them. No collectives on the request path, so per-replica latency
is identical to single-device serving and aggregate QPS scales with
chip count until the host-side queue becomes the bottleneck.

Blind round-robin dies with its first dead replica (every Nth request
stalls), so routing is **health-aware**:

* each replica carries a :class:`~paddle_tpu.serving.breaker.
  CircuitBreaker` fed by batch outcomes and supervision verdicts;
  requests route only to replicas whose breaker allows them, and a
  fleet with no healthy replica fast-rejects with the retryable
  :class:`NoHealthyReplicaError` rather than queueing onto a corpse;
* a :class:`~paddle_tpu.serving.supervisor.ServingSupervisor` watches
  per-replica heartbeats, trips the breaker on a hung dispatch, moves
  that replica's queued *and* in-flight requests to healthy peers
  (failover — safe because ``Request`` resolution is idempotent:
  whichever dispatch finishes first wins, the loser's resolution is
  swallowed), probes half-open breakers with budgeted test traffic,
  restarts replicas that stay dead, and scales the active set from the
  live ``slo.*`` window;
* stragglers are **hedged**: a request still unresolved after the hedge
  delay (p99-derived by default) is re-dispatched to a second healthy
  replica and the first result wins, with total hedges capped at
  ``hedge_budget`` of traffic so the cure can't out-eat the disease.

Beyond failure handling, the fleet has a *lifecycle*: scheduler
preemption (SIGTERM, or the injected ``preempt_replica`` fault) flips a
replica to **draining** — healthy but refusing new work — and migrates
its queued and in-flight requests to peers over the same
``disown_inflight``/``requeue`` deterministic-replay path failover
uses, so a preemption loses zero requests and sampled streams complete
bit-identical to the fault-free run. :meth:`MultiDeviceEngine.
swap_weights` rolls new weights through the fleet one replica at a
time (drain-lite → place state → probe → readmit) without dropping a
request or minting an executable; a quorum-failing checkpoint publish
never swaps in. Every fleet subscribes itself to
``resilience.preempt`` at construction — a process-level SIGTERM
drains every live fleet.

:func:`replicate` is the state mechanic (one Predictor view per device,
sharing the model object, with a per-device executable cache);
:class:`MultiDeviceEngine` is the operational wrapper.
"""
from __future__ import annotations

import copy
import heapq
import threading
import time
import weakref

import concurrent.futures

from .admission import ShedError
from .breaker import CircuitBreaker
from .engine import ServingEngine
from . import metrics
from ..resilience import faults as _faults
from ..resilience import preempt as _preempt

#: live MultiDeviceEngines — /healthz walks this (weak: an un-closed
#: engine can still be collected)
_ACTIVE = weakref.WeakSet()

#: most recent lifecycle event across all fleets (the /snapshot block)
_LAST_LIFECYCLE = None


def last_lifecycle():
    return _LAST_LIFECYCLE

#: floor on the auto hedge delay: below this, hedges fire on normal
#: scheduling jitter and burn the budget on non-stragglers
MIN_HEDGE_S = 0.025


class NoHealthyReplicaError(ShedError):
    """Every replica's breaker is open (or routing-excluded): there is
    no capacity to take this request right now. Transient — the breaker
    cooldown is exactly a retry-after."""


def replicate(predictor, devices=None):
    """One ``Predictor`` view per device: the frozen eval-state pytree
    is ``device_put`` onto each device; the model object and config are
    shared (read-only at serving time); each replica gets its own
    executable cache (XLA executables are device-committed). Default
    devices: every local device."""
    import jax
    devices = list(devices) if devices is not None else jax.local_devices()
    if not devices:
        raise ValueError("replicate: no devices")
    replicas = []
    for d in devices:
        p = copy.copy(predictor)
        p.state = jax.device_put(predictor.state, d)
        p._compiled = {}
        p.device = d
        replicas.append(p)
    return replicas


class _Replica:
    """One slot in the fleet: device + predictor + engine + breaker +
    routing flag, plus the supervision tokens that make hang handling
    exactly-once per dispatch."""

    def __init__(self, index, device, predictor, engine, breaker,
                 active=True):
        self.index = index
        self.device = device
        self.predictor = predictor
        self.engine = engine
        self.breaker = breaker
        self.active = active
        # draining: healthy but refusing NEW work (preemption notice or
        # a rolling weight swap); distinct from an open breaker
        self.draining = False
        self.handled_token = None    # last in-flight dispatch failed over
        self.restart_token = None    # last in-flight dispatch restarted on
        self.restarts = 0

    @property
    def state(self):
        """Routing state for /healthz and the gauges: ``draining``
        masks the (healthy) breaker state while the replica refuses
        admission."""
        return "draining" if self.draining else self.breaker.state


class _Hedger(threading.Thread):
    """Deadline heap + daemon thread: ``schedule`` arms a hedge timer
    per request; when it fires and the request is still unresolved, the
    owner re-dispatches it to a second replica."""

    def __init__(self, owner):
        super().__init__(name="paddle_tpu-serving-hedger", daemon=True)
        self._owner = weakref.ref(owner)
        self._cond = threading.Condition()
        self._heap = []
        self._seq = 0
        self._stop = False

    def schedule(self, request, primary_index, delay_s):
        with self._cond:
            self._seq += 1
            heapq.heappush(self._heap,
                           (time.monotonic() + delay_s, self._seq,
                            request, primary_index))
            self._cond.notify()

    def stop(self):
        with self._cond:
            self._stop = True
            self._cond.notify()

    def run(self):
        while True:
            with self._cond:
                if self._stop:
                    return
                if not self._heap:
                    self._cond.wait(0.1)
                    continue
                due = self._heap[0][0]
                now = time.monotonic()
                if due > now:
                    self._cond.wait(min(due - now, 0.1))
                    continue
                _, _, req, primary = heapq.heappop(self._heap)
            owner = self._owner()
            if owner is None:
                return
            try:
                owner._maybe_hedge(req, primary)
            except Exception:   # noqa: BLE001 - hedging is best-effort;
                pass            # the primary dispatch still owns the future


class MultiDeviceEngine:
    """Health-aware fan-out over per-device :class:`ServingEngine`
    replicas. Same client surface as v1 (``submit``/``run``/``warmup``/
    ``stats``/context manager); engine kwargs apply per replica, so
    ``queue_depth`` and ``max_batch`` are per-device limits.

    Resilience knobs (see docs/serving.md for the full matrix):

    hedge_ms : straggler hedge delay. ``None`` (default) derives it
        from the live ``slo.p99_ms`` window (floored at 25ms); a number
        fixes it; ``0``/``False`` disables hedging.
    hedge_budget : max fraction of submitted traffic that may be
        hedged (default 0.05).
    breaker_threshold / breaker_cooldown_s / half_open_probes :
        per-replica :class:`CircuitBreaker` tuning.
    inflight_timeout_ms : a dispatch older than this is declared hung —
        breaker trips, batch fails over. ``None`` defaults to 4× the
        engine ``deadline_ms`` when set, else 2000ms.
    supervise : run the :class:`ServingSupervisor` control loop
        (default True; tests drive ticks manually with False).
    min_replicas / initial_active : scaling bounds — the supervisor
        never deactivates below ``min_replicas``; ``initial_active``
        starts the fleet smaller than the device count and lets the
        goodput floor scale it up.
    """

    def __init__(self, predictor, devices=None, hedge_ms=None,
                 hedge_budget=0.05, breaker_threshold=3,
                 breaker_cooldown_s=2.0, half_open_probes=1,
                 inflight_timeout_ms=None, supervise=True,
                 supervisor_interval_s=0.25, min_replicas=1,
                 initial_active=None, restart_after_s=None,
                 tokens_floor=None, **engine_kwargs):
        self.predictor = predictor
        self._engine_kwargs = dict(engine_kwargs)
        self._breaker_kwargs = dict(
            failure_threshold=breaker_threshold,
            cooldown_s=breaker_cooldown_s,
            half_open_probes=half_open_probes)
        preds = self._replicate(predictor, devices)
        self._replicas = []
        for i, p in enumerate(preds):
            self._replicas.append(self._make_replica(i, p))
        if initial_active is not None:
            for r in self._replicas[int(initial_active):]:
                r.active = False
        self.min_replicas = max(1, int(min_replicas))
        self._rr_lock = threading.Lock()
        self._rr = 0
        # hedging
        if hedge_ms is None:
            self._hedge_fixed = None
            self._hedge_delay_s = 2 * MIN_HEDGE_S   # until p99 exists
        elif not hedge_ms:                          # 0 / False
            self._hedge_fixed = 0.0
            self._hedge_delay_s = 0.0
        else:
            self._hedge_fixed = float(hedge_ms) / 1e3
            self._hedge_delay_s = self._hedge_fixed
        self.hedge_budget = float(hedge_budget)
        self._hedge_lock = threading.Lock()
        self._submitted = 0
        self._hedged = 0
        self._hedge_wins = 0
        self._failovers = 0
        self._hedger = None
        if self._hedge_delay_s or self._hedge_fixed is None:
            self._hedger = _Hedger(self)
            self._hedger.start()
        # supervision
        if inflight_timeout_ms is None:
            dl = engine_kwargs.get("deadline_ms")
            inflight_timeout_ms = 4 * dl if dl else 2000.0
        self.inflight_timeout_s = float(inflight_timeout_ms) / 1e3
        self._warm_sigs = ()
        self.supervisor = None
        if supervise:
            from .supervisor import ServingSupervisor
            self.supervisor = ServingSupervisor(
                self, interval_s=supervisor_interval_s,
                restart_after_s=restart_after_s,
                tokens_floor=tokens_floor)
        # lifecycle: served weights version (stamped into reqtrace
        # records), the fleet's last lifecycle event, and the process
        # preemption subscription — SIGTERM drains this fleet; the
        # subscription holds the fleet weakly so an un-closed engine
        # can still be collected
        self.weights_version = 0
        for r in self._replicas:
            r.engine.weights_version = 0
        self._lifecycle = None
        self._swap_lock = threading.Lock()
        _self_ref = weakref.ref(self)

        def _on_preempt(signum, _ref=_self_ref):
            owner = _ref()
            if owner is not None:
                owner.drain_fleet(reason=f"preempt:{signum}")

        self._preempt_cb = _preempt.subscribe(_on_preempt)
        _ACTIVE.add(self)
        metrics.record_active_replicas(
            sum(1 for r in self._replicas if r.active))

    # -- replica construction hooks (overridden by the decode fleet) -------

    def _replicate(self, predictor, devices):
        """State mechanic: one predictor view per device. The decode
        fleet (``generate.MultiDecodeEngine``) overrides this with
        ``replicate_decode`` — same fan-out spine, different payload."""
        return replicate(predictor, devices)

    def _new_engine(self, predictor, index, on_outcome):
        """Per-replica engine factory — the other decode-fleet seam."""
        return ServingEngine(predictor, replica_id=index,
                             on_outcome=on_outcome, **self._engine_kwargs)

    def _make_replica(self, index, predictor):
        breaker = CircuitBreaker(name=str(index), **self._breaker_kwargs)

        def _outcome(ok, exc, _b=breaker):
            if ok:
                _b.record_success()
            else:
                _b.record_failure(repr(exc))

        engine = self._new_engine(predictor, index, _outcome)
        return _Replica(index, getattr(predictor, "device", None),
                        predictor, engine, breaker)

    # -- compat views ------------------------------------------------------

    @property
    def engines(self):
        return [r.engine for r in self._replicas]

    @property
    def replicas(self):
        return [r.predictor for r in self._replicas]

    # -- routing -----------------------------------------------------------

    def _pick_replica(self, exclude=()):
        """Next active replica whose breaker admits traffic, round-robin
        from the cursor. ``allow()`` on a half-open breaker consumes one
        probe slot — it's only called on replicas actually considered.
        Raises :class:`NoHealthyReplicaError` when nobody can take it."""
        with self._rr_lock:
            n = len(self._replicas)
            order = [(self._rr + k) % n for k in range(n)]
            self._rr = (self._rr + 1) % n
        for idx in order:
            r = self._replicas[idx]
            if not r.active or r.draining or idx in exclude:
                continue
            if r.breaker.allow():
                return r
        states = {r.index: r.state for r in self._replicas}
        raise NoHealthyReplicaError(
            f"no healthy replica (breakers: {states}); retry after "
            f"{self._breaker_kwargs['cooldown_s'] * 1e3:.0f}ms",
            retry_after_ms=self._breaker_kwargs["cooldown_s"] * 1e3,
            level=3)

    def submit(self, *inputs, deadline_ms=None, priority=None,
               trace=None):
        rep = self._pick_replica()
        req = rep.engine.make_request(inputs, deadline_ms=deadline_ms,
                                      priority=priority, trace=trace)
        fut = rep.engine.submit_request(req)
        with self._hedge_lock:
            self._submitted += 1
        delay = self._hedge_delay_s
        if self._hedger is not None and delay and len(self._replicas) > 1:
            self._hedger.schedule(req, rep.index, delay)
        return fut

    def run(self, *inputs, deadline_ms=None, timeout=None, priority=None):
        return self.submit(*inputs, deadline_ms=deadline_ms,
                           priority=priority).result(timeout)

    # -- hedging -----------------------------------------------------------

    def _maybe_hedge(self, req, primary_index):
        """Hedge timer fired: if the request is still unresolved and the
        budget allows, re-dispatch it to a different healthy replica and
        let the first resolution win."""
        if req.future.done():
            return
        with self._hedge_lock:
            if self._hedged >= self.hedge_budget * self._submitted:
                return
            self._hedged += 1
        try:
            rep = self._pick_replica(exclude=(primary_index,))
        except NoHealthyReplicaError:
            with self._hedge_lock:
                self._hedged -= 1   # unfired: give the budget back
            return
        from .batcher import Request
        ptr = req.trace
        shadow = Request(req.inputs, req.n, req.signature,
                         deadline=req.deadline, priority=req.priority,
                         seq_real=req.seq_real, seq_padded=req.seq_padded,
                         # the shadow rides the SAME trace context as a
                         # hedge attempt: whichever resolution wins the
                         # shared done-latch emits the one record
                         trace=(None if ptr is None else
                                ptr.ctx.attempt("hedge", rep.index)))
        if ptr is not None:
            ptr.hop("hedge", replica=rep.index)
        metrics.record_hedge(replica=rep.index)

        def _on_shadow_done(sf, _req=req, _idx=rep.index):
            if sf.cancelled() or sf.exception() is not None:
                return          # primary still owns the future
            try:
                _req.future.set_result(sf.result())
            except concurrent.futures.InvalidStateError:
                return          # primary won the race
            with self._hedge_lock:
                self._hedge_wins += 1
            metrics.record_hedge_win(replica=_idx)

        shadow.future.add_done_callback(_on_shadow_done)
        try:
            rep.engine.submit_request(shadow)
        except ShedError:
            with self._hedge_lock:
                self._hedged -= 1   # shadow shed at admission: not a hedge
        except RuntimeError:
            pass                    # replica closed under us

    def _refresh_hedge_delay(self, p99_ms):
        """Supervisor tick: re-derive the auto hedge delay from the live
        p99 (a hedge should fire only for genuine stragglers)."""
        if self._hedge_fixed is not None:
            return
        if p99_ms:
            self._hedge_delay_s = max(MIN_HEDGE_S, float(p99_ms) / 1e3)

    # -- failover / drain / restart (supervisor verdicts) ------------------

    def _migrate(self, replica, hop, reason=""):
        """Move a replica's queued and in-flight requests to healthy
        peers (the shared spine under failover AND graceful drain). The
        in-flight group is *disowned* first, so even if the source
        dispatch eventually completes, whichever resolution lands first
        wins and the other is swallowed — exactly once, either way.
        Decode requests regenerate bit-identically on the adopting
        replica (counter-based sampling — see ``disown_inflight``)."""
        moved = self._disown(replica)
        moved += replica.engine.steal_pending()
        moved = [r for r in moved if not r.future.done()]
        if not moved:
            return 0
        for r in moved:
            tr = getattr(r, "trace", None)
            if tr is not None:
                tr.hop(hop, replica=replica.index, reason=reason)
        try:
            target = self._pick_replica(exclude=(replica.index,))
        except NoHealthyReplicaError as e:
            for r in moved:
                r.resolve_exception(e)
            return len(moved)
        target.engine.requeue(moved)
        return len(moved)

    def _disown(self, replica):
        """Seam: how in-flight work leaves a replica during migration.
        The disaggregated decode pool overrides this to carry each
        sequence's KV segment along (``disown_inflight(export_kv=True)``)
        so a drained sequence resumes mid-stream instead of
        re-prefilling."""
        return replica.engine.disown_inflight()

    def _failover(self, replica, reason=""):
        """Move a tripped replica's work to healthy peers and count it."""
        moved = self._migrate(replica, "failover", reason)
        if moved:
            with self._hedge_lock:
                self._failovers += 1
            metrics.record_failover(replica.index, moved)
        return moved

    # -- graceful drain (preemption / rolling swap) ------------------------

    def _record_lifecycle(self, event, **fields):
        global _LAST_LIFECYCLE
        entry = {"event": event, "t": time.time(), **fields}
        self._lifecycle = entry
        _LAST_LIFECYCLE = entry
        metrics.record_lifecycle(event, **fields)

    def _resolve_replica(self, replica):
        if isinstance(replica, _Replica):
            return replica
        return self._replicas[int(replica)]

    def _has_peer(self, exclude_index):
        """Is there anywhere for migrated work to land?"""
        return any(r.active and not r.draining
                   and r.breaker.state != "open"
                   and r.index != exclude_index for r in self._replicas)

    def drain_replica(self, replica, reason="preempt"):
        """Preemption notice for ONE replica: stop admitting, migrate
        its queued and in-flight work to healthy peers (zero lost
        requests — streams regenerate bit-identically). With no healthy
        peer the replica keeps its work and finishes it while refusing
        new admissions. Returns the number of requests migrated."""
        r = self._resolve_replica(replica)
        if r.draining:
            return 0
        r.draining = True
        moved = self._migrate(r, "drain", reason) \
            if self._has_peer(r.index) else 0
        self._record_lifecycle("drain", replica=r.index, reason=reason,
                               moved=moved)
        return moved

    def undrain_replica(self, replica, reason=""):
        """Readmit a drained replica into the rotation."""
        r = self._resolve_replica(replica)
        if not r.draining:
            return
        r.draining = False
        self._record_lifecycle("undrain", replica=r.index, reason=reason)

    def drain_fleet(self, reason="preempt"):
        """Process-level preemption notice (SIGTERM): EVERY replica
        stops admitting new work; queued and in-flight requests run to
        completion in place (there is no healthy peer to migrate to —
        the whole process is going away). Subsequent submits shed with
        :class:`NoHealthyReplicaError`. Poll :meth:`drained` / block on
        :meth:`drain_wait` before exiting."""
        flipped = [r.index for r in self._replicas if not r.draining]
        for r in self._replicas:
            r.draining = True
        self._record_lifecycle("drain_fleet", reason=reason,
                               replicas=len(flipped))
        return len(flipped)

    def drained(self, now=None):
        """True when no replica holds queued or in-flight work."""
        for r in self._replicas:
            h = r.engine.heartbeat(now)
            if h["queue_depth"] or h.get("active"):
                return False
        return True

    def drain_wait(self, timeout_s=10.0, poll_s=0.01):
        """Block until :meth:`drained` (or timeout); returns the final
        drained verdict."""
        deadline = time.monotonic() + float(timeout_s)
        while not self.drained():
            if time.monotonic() >= deadline:
                return False
            time.sleep(poll_s)
        return True

    # -- live weight hot-swap ----------------------------------------------

    def _replica_empty(self, r, timeout_s, poll_s=0.005):
        """Wait until one replica holds no queued or in-flight work."""
        deadline = time.monotonic() + float(timeout_s)
        while True:
            h = r.engine.heartbeat()
            if not h["queue_depth"] and not h.get("active") \
                    and h["inflight_age_s"] is None:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(poll_s)

    def _resolve_swap_source(self, source, step):
        """Turn a swap source into a host state tree.

        ``source`` is a live pytree (served as-is), a sharded checkpoint
        directory path, or a ``CheckpointManager`` (+ ``step``) whose
        published step directory is resolved. Directory sources must
        pass the full quorum :func:`io.sharded.validate` — a corrupt
        publish is quarantined (``<dir>.corrupt``), counted
        (``serving.lifecycle.swap_refused``) and never swaps in."""
        import os
        from ..io import sharded as _sharded
        dirname = None
        if hasattr(source, "_sharded_path"):
            if step is None:
                raise ValueError(
                    "swap_weights(CheckpointManager) needs step=")
            dirname = source._sharded_path(step)
        elif isinstance(source, (str, os.PathLike)):
            dirname = os.fspath(source)
        if dirname is None:
            return source     # a live tree
        # the publish-corruption fault garbles one committed shard just
        # before the swap reads it — quorum validation must catch it
        spec = _faults.fire("publish_corrupt", None) \
            if _faults.enabled() else None
        if spec is not None:
            shards = sorted(f for f in os.listdir(dirname)
                            if f.endswith(".npy"))
            if shards:
                _faults.garble_file(os.path.join(dirname, shards[0]))
        ok, why = _sharded.validate(dirname)
        if not ok:
            quarantine = dirname + ".corrupt"
            try:
                os.replace(dirname, quarantine)
            except OSError:
                quarantine = None
            self._record_lifecycle("swap_refused", source=dirname,
                                   why=why, quarantined=quarantine)
            raise ValueError(
                f"swap_weights: publish {dirname} failed quorum "
                f"validation ({why}); quarantined, serving version "
                f"{self.weights_version} unchanged")
        state, _manifest = _sharded.load_state(dirname, verify=False)
        # a CheckpointManager publish wraps the tree ({"step":…,
        # "model": …}); unwrap to the served payload
        if isinstance(state, dict) and "model" in state:
            state = state["model"]
        return state

    def _check_swap_shapes(self, new_tree):
        """Same-shape contract: the swap must not mint executables, so
        treedef and every leaf's (shape, dtype) must match the serving
        template."""
        import jax
        import numpy as np
        old_leaves, old_def = jax.tree_util.tree_flatten(
            self.predictor.state)
        new_leaves, new_def = jax.tree_util.tree_flatten(new_tree)
        if old_def != new_def:
            return f"tree structure mismatch: {new_def} != {old_def}"
        for i, (a, b) in enumerate(zip(old_leaves, new_leaves)):
            sa, sb = np.shape(a), np.shape(b)
            if sa != sb:
                return f"leaf {i} shape mismatch: {sb} != {sa}"
        return None

    def swap_weights(self, source, step=None, version=None, probe=True,
                     drain_timeout_s=10.0, probe_timeout_s=2.0):
        """Roll new weights through the live fleet, one replica at a
        time, without dropping a request or minting an executable.

        Per replica: drain-lite (stop admitting; migrate its queued +
        in-flight work to peers when any exist, else let it finish in
        place), ``device_put`` the new state onto its device, half-open
        style :meth:`~ServingEngine.probe` with the fresh weights, then
        readmit. State rides the executables as an *argument* (the
        state-as-argument jit contract), so a same-shape swap reuses
        every compiled executable — ``executables()`` before and after
        must agree.

        ``source``: a live state pytree, a sharded checkpoint directory,
        or a ``CheckpointManager`` with ``step=`` — directory sources
        must pass quorum validation (see :meth:`_resolve_swap_source`).
        ``version`` defaults to ``weights_version + 1``. On a probe
        failure the whole roll is unwound — the failing replica AND
        every already-swapped replica get their old state back — so the
        fleet is never left serving mixed weights. Returns the new
        version."""
        import jax
        with self._swap_lock:
            state = self._resolve_swap_source(source, step)
            why = self._check_swap_shapes(state)
            if why is not None:
                self._record_lifecycle("swap_refused", why=why)
                raise ValueError(f"swap_weights: {why}")
            new_version = (int(version) if version is not None
                           else self.weights_version + 1)
            swapped = []   # (replica, old_state) — rollback ledger
            for r in self._replicas:
                was_draining = r.draining
                r.draining = True
                try:
                    if self._has_peer(r.index):
                        self._migrate(r, "swap", reason="hot_swap")
                    self._replica_empty(r, drain_timeout_s)
                    old_state = r.predictor.state
                    r.predictor.state = jax.device_put(state, r.device)
                    if probe:
                        ok = r.engine.probe(timeout_s=probe_timeout_s)
                        # None = never served, nothing to replay: pass
                        if ok is False:
                            # unwind the WHOLE roll: a half-swapped
                            # fleet serving mixed weights breaks the
                            # bit-reproducibility contract
                            r.predictor.state = old_state
                            for rb, rb_old in swapped:
                                rb.predictor.state = rb_old
                                rb.engine.weights_version = \
                                    self.weights_version
                            self._record_lifecycle(
                                "swap_failed", replica=r.index,
                                version=new_version,
                                rolled_back=[x.index for x, _ in swapped])
                            raise RuntimeError(
                                f"swap_weights: probe failed on replica "
                                f"{r.index} with version {new_version}; "
                                f"the roll was unwound and the fleet "
                                f"keeps serving version "
                                f"{self.weights_version}")
                    r.engine.weights_version = new_version
                    swapped.append((r, old_state))
                finally:
                    r.draining = was_draining
            # the template feeds _restart/_replicate: future rebuilds
            # must come up on the new version
            self.predictor.state = state
            self.weights_version = new_version
            metrics.record_weights_version(new_version)
            self._record_lifecycle(
                "swap", version=new_version,
                source=("tree" if not isinstance(source, (str,))
                        and not hasattr(source, "_sharded_path")
                        else "checkpoint"),
                replicas=len(swapped))
            return new_version

    def _restart(self, replica):
        """Re-``replicate()`` state onto the replica's device, swap in a
        fresh engine (warmed with the remembered signatures), and close
        the old one in the background with a bounded join — its drain
        thread may be stuck forever."""
        old_engine = replica.engine
        fresh_pred = self._replicate(self.predictor, [replica.device])[0]
        fresh = self._make_replica(replica.index, fresh_pred)
        # keep the ORIGINAL breaker (state + flap history): the restarted
        # engine stays open until a probe or budgeted request closes it
        def _outcome(ok, exc, _b=replica.breaker):
            if ok:
                _b.record_success()
            else:
                _b.record_failure(repr(exc))
        fresh.engine.on_outcome = _outcome
        if self._warm_sigs:
            try:
                fresh.engine.warmup(*self._warm_sigs)
            except Exception:   # noqa: BLE001 - warm lazily instead
                pass
        fresh.engine.start()
        replica.predictor = fresh.predictor
        replica.engine = fresh.engine
        replica.restarts += 1
        replica.restart_token = None
        # drop the dead engine's per-replica gauges: the next sampler
        # tick re-mints them from the live breaker, so a stale "open"
        # from before the restart can't linger in rollups
        metrics.clear_replica_series(replica.index)
        metrics.record_replica_restart(replica.index)
        threading.Thread(
            target=lambda: old_engine.close(drain=False, timeout=1.0),
            name="paddle_tpu-serving-reap", daemon=True).start()

    # -- scaling (supervisor verdicts) -------------------------------------

    def _active_count(self):
        return sum(1 for r in self._replicas if r.active)

    def _activate_one(self):
        for r in self._replicas:
            if not r.active and not r.draining:
                r.active = True
                metrics.record_active_replicas(self._active_count())
                return r
        return None

    def _deactivate_one(self):
        if self._active_count() <= self.min_replicas:
            return None
        for r in reversed(self._replicas):
            if r.active and not r.draining:
                r.active = False
                # drain its queue onto the survivors
                moved = [q for q in r.engine.steal_pending()
                         if not q.future.done()]
                if moved:
                    try:
                        self._pick_replica(
                            exclude=(r.index,)).engine.requeue(moved)
                    except NoHealthyReplicaError:
                        r.engine.requeue(moved)   # undo: keep serving
                        r.active = True
                        return None
                metrics.record_active_replicas(self._active_count())
                return r
        return None

    # -- fleet lifecycle ---------------------------------------------------

    def warmup(self, *signatures):
        """Warm every replica (each compiles its own device-committed
        executables); the signatures are remembered so a restarted
        replica re-warms before taking traffic. Returns total fresh
        executables."""
        self._warm_sigs = signatures
        return sum(r.engine.warmup(*signatures) for r in self._replicas)

    def start(self):
        for r in self._replicas:
            r.engine.start()

    def close(self, drain=True, timeout=None):
        if self.supervisor is not None:
            self.supervisor.stop()
        if self._hedger is not None:
            self._hedger.stop()
        _preempt.unsubscribe(self._preempt_cb)
        _ACTIVE.discard(self)
        for r in self._replicas:
            # a hung replica must not hold close() hostage: bound the
            # join (its stranded futures fail rather than strand)
            t = timeout
            if t is None and drain:
                t = 10.0
            r.engine.close(drain=drain, timeout=t)
            # closed replicas leave no stale per-replica gauges behind
            metrics.clear_replica_series(r.index)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.close()

    # -- observability -----------------------------------------------------

    def stats(self):
        """Aggregate across replicas, with the per-replica breakdown
        under ``"replicas"`` and the resilience tallies alongside."""
        per = [r.engine.stats() for r in self._replicas]
        agg = {k: sum(s[k] for s in per)
               for k in per[0] if isinstance(per[0][k], (int, float))}
        agg["replicas"] = per
        agg["devices"] = [str(r.device) for r in self._replicas]
        with self._hedge_lock:
            agg["hedged"] = self._hedged
            agg["hedge_wins"] = self._hedge_wins
            agg["failovers"] = self._failovers
        agg["restarts"] = sum(r.restarts for r in self._replicas)
        agg["active_replicas"] = self._active_count()
        agg["draining_replicas"] = sum(
            1 for r in self._replicas if r.draining)
        agg["weights_version"] = self.weights_version
        agg["breakers"] = {r.index: r.state for r in self._replicas}
        return agg

    def health(self, now=None):
        """The /healthz ``serving`` block: per-replica routing state
        (``state`` is the breaker state, or ``draining`` — a healthy
        replica refusing admission is NOT unhealthy) and heartbeat
        ages, plus ``all_open`` (no replica can take traffic → the
        endpoint answers 503; a fully draining fleet reads all_open
        because it really is refusing traffic)."""
        now = time.monotonic() if now is None else now
        reps = []
        any_admitting = False
        for r in self._replicas:
            h = r.engine.heartbeat(now)
            if r.active and not r.draining and r.breaker.state != "open":
                any_admitting = True
            reps.append({
                "replica": r.index,
                "device": str(r.device),
                "state": r.state,
                "breaker": r.breaker.state,
                "draining": bool(r.draining),
                "active": bool(r.active),
                "queue_depth": h["queue_depth"],
                "inflight": h.get("active", 0),
                "inflight_age_s": None if h["inflight_age_s"] is None
                else round(h["inflight_age_s"], 3),
                "heartbeat_age_s": round(h["last_ok_age_s"], 3),
                "restarts": r.restarts,
            })
        out = {"replicas": reps, "all_open": not any_admitting,
               "active_replicas": self._active_count(),
               "weights_version": self.weights_version}
        if self._lifecycle is not None:
            out["last_lifecycle"] = self._lifecycle
        if self.supervisor is not None:
            out["supervisor"] = self.supervisor.last_decision()
        return out


def health():
    """Health blocks for every live MultiDeviceEngine (what
    ``monitor.export.health_payload`` embeds under ``serving``)."""
    return [eng.health() for eng in list(_ACTIVE)]


def publish_gauges():
    """Sampler tick: republish per-replica breaker state and the active
    count (transitions set the gauges too, but a tick keeps the
    open→half_open cooldown promotion visible without traffic)."""
    from .. import monitor as _monitor
    if not _monitor.enabled():
        return
    for eng in list(_ACTIVE):
        metrics.record_active_replicas(eng._active_count())
        metrics.record_weights_version(eng.weights_version)
        for r in eng._replicas:
            _monitor.gauge(f"serving.breaker_state.{r.index}").set(
                metrics._BREAKER_STATE_NUM.get(r.state, -1))
