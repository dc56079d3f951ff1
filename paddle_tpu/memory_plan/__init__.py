"""paddle_tpu.memory_plan — memory as a planned resource.

PR 12 made memory *observable*: ``monitor.memory.simulate()`` predicts
an executable's HBM peak pre-flight and attributes it by buffer class.
This package makes memory *managed* — three composable mechanisms plus
an auto-picker that turns the predicted-peak model into decisions:

* **Activation rematerialization** (``remat``): ``jax.checkpoint``
  around layer forwards / traced step bodies with named policies —
  ``"none"`` | ``"dots"`` (save dot outputs, recompute elementwise) |
  ``"full"`` (save only the inputs) — or MeshPlan-style per-layer
  regex rules ``((pattern, policy), ...)``, first match wins. Exact:
  the backward replays the identical ops, losses are bit-identical.
  Between ``"full"`` and ``"dots"`` stands what ``jit.recompute(layer,
  x)`` means when no policy is named, ``KERNEL_RESULTS``: the block's
  activations are made again, a kernel's saved result (the flash
  kernels' o and statistic rows, the loss and three gradients of
  ``F.dsa_indexer_loss``'s one pass and the packed bits of
  ``F.dsa_select``'s selection, which they leave under names) is not.
  ``remat=`` and the layer hook always name ``"full"`` or ``"dots"``.
* **Optimizer-state host offload** (``offload``): pages the flat
  ``ParamArena`` Adam moments to host RAM after each apply and
  prefetches them back during the next step's forward/backward on a
  dedicated worker thread (the grad-sync comm-worker pattern), so the
  transfers sit on their own trace track (``offload.d2h`` /
  ``offload.h2d``) and only the un-hidden remainder shows up in
  ``mem.offload.exposed_wait_s``. Exact: paging is a bit-preserving
  round trip — and it implies the *split step* (fwd/bwd jitted
  separately from the eager fused apply) so the training executable
  never carries the optimizer state as an argument at all.
* **bf16 device-resident params over fp32 master weights**
  (``master_weights``): the arena keeps the fp32 flat buffer (the
  master — checkpoints stay exact fp32) and binds *bf16 views* inside
  traced steps while the step body runs under ``amp.auto_cast``;
  grads are cast back to fp32 by ``pack_grads`` and the update
  applies to the master. Tolerance-gated: not bit-identical.

``plan_memory(auto=True)`` closes the loop (ROADMAP item 4): simulate
the compiled baseline, derive the candidate ladder (none → dots →
full → full+offload), score each by predicted step-time overhead
(recompute flops on the roofline, offload bytes over the host link),
refuse offload when ``mem.host.headroom_bytes`` can't take the paged
state, pick the cheapest policy that fits ``device_hbm_limit()``, and
record the decision in the monitor ledger exactly like
``planner.plan(auto=True)`` does.
"""
from __future__ import annotations

import contextlib
import functools
import os
import re
import threading
import time

__all__ = [
    "MemoryPolicy", "resolve", "policy_key", "checkpoint_policy",
    "KERNEL_RESULTS",
    "remat_scope", "current_remat", "policy_for_layer",
    "install_layer_hook",
    "host_mem_limit", "host_headroom_bytes", "host_link_bandwidth",
    "measure_host_bandwidth", "ArenaOffloader", "attach_offload",
    "detach_offload",
    "plan_memory", "candidate_table", "last_decision", "reset",
]

_REMAT_NAMES = ("none", "dots", "full")

# jit.recompute's policy when none is named (not a ``remat=`` name)
KERNEL_RESULTS = "kernel_results"


def _canon_remat(pol):
    """Canonicalize a remat spec: None/"none" → None, a policy name →
    itself, anything iterable → a hashable ((pattern, name), ...) rule
    tuple (PR 11's MeshPlan rule idiom)."""
    if pol is None or pol == "none":
        return None
    if isinstance(pol, str):
        if pol not in _REMAT_NAMES:
            raise ValueError(
                f"unknown remat policy {pol!r}: expected one of "
                f"{_REMAT_NAMES} or ((pattern, policy), ...) rules")
        return pol
    rules = []
    for item in pol:
        pat, name = item
        name = None if name in (None, "none") else str(name)
        if name is not None and name not in ("dots", "full"):
            raise ValueError(f"unknown remat policy {name!r} in rule "
                             f"({pat!r}, {name!r})")
        rules.append((str(pat), name))
    return tuple(rules)


class MemoryPolicy:
    """One resolved memory policy: what to remat, whether to page the
    optimizer state to host, whether params go device-bf16 over an
    fp32 master. Hashable + stably keyed so it can join jit/Executor
    cache keys (a policy toggle is exactly one recompile)."""

    __slots__ = ("remat", "offload", "master_weights")

    def __init__(self, remat=None, offload=False, master_weights=False):
        object.__setattr__(self, "remat", _canon_remat(remat))
        object.__setattr__(self, "offload", bool(offload))
        object.__setattr__(self, "master_weights", bool(master_weights))

    def __setattr__(self, name, value):
        raise AttributeError("MemoryPolicy is immutable")

    def key(self):
        return policy_key(self)

    def __repr__(self):
        return (f"MemoryPolicy(remat={self.remat!r}, "
                f"offload={self.offload}, "
                f"master_weights={self.master_weights})")

    def __eq__(self, other):
        return (isinstance(other, MemoryPolicy)
                and self.remat == other.remat
                and self.offload == other.offload
                and self.master_weights == other.master_weights)

    def __hash__(self):
        return hash((self.remat, self.offload, self.master_weights))


def resolve(memory):
    """Coerce a user-facing ``memory=`` knob into a MemoryPolicy.

    Accepts None, ``"auto"`` (returned verbatim — the caller defers to
    :func:`plan_memory` after the baseline compile), a remat name
    (``"none"|"dots"|"full"``), ``"offload"``, a rule tuple, a dict of
    MemoryPolicy fields, or an already-built MemoryPolicy."""
    if memory is None:
        return None
    if isinstance(memory, MemoryPolicy):
        return memory
    if isinstance(memory, str):
        if memory == "auto":
            return "auto"
        if memory == "offload":
            return MemoryPolicy(offload=True)
        return MemoryPolicy(remat=memory)
    if isinstance(memory, dict):
        bad = set(memory) - {"remat", "offload", "master_weights"}
        if bad:
            raise ValueError(f"memory=: unknown fields {sorted(bad)}; "
                             "expected remat/offload/master_weights")
        return MemoryPolicy(**memory)
    return MemoryPolicy(remat=memory)   # rule tuple


def policy_key(pol):
    """Short stable string for cache keys and ledger rows."""
    if pol is None:
        return "none"
    if pol == "auto":
        return "auto"
    r = pol.remat
    if r is None:
        if not pol.offload and not pol.master_weights:
            return "none"  # all-defaults policy == no policy
        rk = "none"
    elif isinstance(r, str):
        rk = r
    else:
        rk = "rules:" + ";".join(f"{p}->{n or 'none'}" for p, n in r)
    parts = [f"remat={rk}"]
    if pol.offload:
        parts.append("offload")
    if pol.master_weights:
        parts.append("bf16master")
    return ",".join(parts)


@functools.cache
def _kernel_results_policy():
    # ONE function object a process: JAX caches a checkpoint's partial
    # evaluation of an inner ``jax.jit`` by the policy's identity, and a
    # policy made anew for every block gives every block a jaxpr (and a
    # Mosaic lowering) of its own of a kernel behind a module-level jit
    import jax
    from ..ops.pallas.flash_attention import RESULT_NAMES
    from ..ops import sparse_attention as sa    # the loss's pass, the bits
    return jax.checkpoint_policies.save_only_these_names(
        *RESULT_NAMES, *sa.RESULT_NAMES, *sa.SELECTION_NAMES)


def checkpoint_policy(name):
    """Map a remat policy name onto ``jax.checkpoint``'s ``policy=``:
    ``"full"`` → None (save nothing but the inputs), ``"dots"`` →
    ``jax.checkpoint_policies.checkpoint_dots`` (save matmul outputs,
    recompute the elementwise tail), ``KERNEL_RESULTS`` →
    ``save_only_these_names`` over the names the flash kernels, the
    indexer's loss and the selection give their results (a block without
    such a call saves what ``"full"`` saves). Callers only reach here when a
    checkpoint is actually being placed — ``"none"`` means *no*
    ``jax.checkpoint`` at all, which is not this function's job."""
    if name in (None, "none", "full"):
        return None
    if name == "dots":
        import jax
        return jax.checkpoint_policies.checkpoint_dots
    if name == KERNEL_RESULTS:
        return _kernel_results_policy()
    raise ValueError(f"unknown remat policy {name!r}")


# ---------------------------------------------------------------------------
# ambient remat scope + the Layer.__call__ hook

_tls = threading.local()


@contextlib.contextmanager
def remat_scope(policy):
    """Ambient remat policy for every layer called inside — how
    ``to_static(remat=)`` reaches the layers of a traced step body.
    Nested scopes shadow; ``None`` disables."""
    pol = _canon_remat(policy)
    if pol is not None:
        install_layer_hook()
    prev = getattr(_tls, "remat", None)
    _tls.remat = pol
    try:
        yield
    finally:
        _tls.remat = prev


def current_remat():
    return getattr(_tls, "remat", None)


def policy_for_layer(layer, pol):
    """Effective checkpoint-policy name for one layer under ``pol``: a
    plain name applies to the outermost layer reached (the whole
    subtree lands in one checkpoint — nested calls are suppressed by
    the recompute guard), a rule tuple is matched with ``re.search``
    against ``name_scope:ClassName``, first match wins."""
    if pol is None:
        return None
    if isinstance(pol, str):
        return None if pol == "none" else pol
    hay = f"{getattr(layer, '_name_scope', '')}:{type(layer).__name__}"
    for pat, name in pol:
        if re.search(pat, hay):
            return name
    return None


def _layer_remat_hook(layer, args, kwargs):
    """Installed as ``nn.layer._remat_hook`` and consulted by
    ``Layer.__call__``. Returns NotImplemented to mean "no remat here,
    run the normal forward"."""
    pol = getattr(layer, "_remat", None)
    if pol is not None:
        name = policy_for_layer(layer, _canon_remat(pol))
    else:
        name = policy_for_layer(layer, current_remat())
    if name is None:
        return NotImplemented
    from ..tensor import Tensor
    for a in args:
        if a is not None and not isinstance(a, Tensor):
            return NotImplemented   # recompute threads Tensor args only
    for v in kwargs.values():
        if isinstance(v, Tensor):
            return NotImplemented
    from .. import jit as _jit
    return _jit.recompute(layer, *args, policy=name, **kwargs)


_hook_installed = False


def install_layer_hook():
    """Arm the Layer.__call__ remat hook (idempotent). Mirrors
    ``tensor._arena_hook``'s cost discipline: until the first remat
    feature is used the hook is None and layers pay nothing."""
    global _hook_installed
    if _hook_installed:
        return
    from ..nn import layer as _layer_mod
    _layer_mod._remat_hook = _layer_remat_hook
    _hook_installed = True


# ---------------------------------------------------------------------------
# host-side budget + host link bandwidth

def host_mem_limit():
    """Host-memory budget in bytes: $PADDLE_TPU_HOST_MEM_LIMIT_BYTES,
    else autodetected /proc/meminfo MemTotal, else None (no budget)."""
    env = os.environ.get("PADDLE_TPU_HOST_MEM_LIMIT_BYTES")
    if env:
        try:
            return int(float(env))
        except ValueError:
            pass
    try:
        with open("/proc/meminfo", encoding="ascii",
                  errors="replace") as fh:
            for line in fh:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except Exception:
        pass
    return None


def host_headroom_bytes():
    """limit − current RSS, or None when either side is unknown. The
    sampler publishes the same number as ``mem.host.headroom_bytes``;
    the auto-picker uses it to refuse offload the host can't hold."""
    limit = host_mem_limit()
    if limit is None:
        return None
    from ..monitor.sampler import _host_rss_bytes
    rss = _host_rss_bytes()
    if rss is None:
        return None
    return limit - rss


# PCIe-class defaults when nothing is measured or pinned (bytes/s)
_HOST_LINK_DEFAULT = {"tpu": 16e9, "gpu": 16e9, "cpu": 4e9}

_measured_bw = None


def host_link_bandwidth(gbps=None):
    """Host↔device link bandwidth (bytes/s) for the offload cost
    model: explicit arg → $PADDLE_TPU_HOST_LINK_GBPS → the cached
    :func:`measure_host_bandwidth` result → a PCIe-class default."""
    if gbps is not None:
        return float(gbps) * 1e9
    env = os.environ.get("PADDLE_TPU_HOST_LINK_GBPS")
    if env:
        return float(env) * 1e9
    if _measured_bw is not None:
        return _measured_bw
    try:
        import jax
        plat = str(jax.local_devices()[0].platform)
    except Exception:
        plat = "cpu"
    return _HOST_LINK_DEFAULT.get(plat, 4e9)


def measure_host_bandwidth(n_bytes=1 << 24, repeats=3):
    """Measured D2H+H2D round-trip bandwidth (bytes/s), cached so
    :func:`host_link_bandwidth` serves it from then on. Best-of-N
    (the first lap doubles as warmup)."""
    global _measured_bw
    import jax
    import jax.numpy as jnp
    import numpy as np
    n = max(1, int(n_bytes) // 4)
    dev = jax.device_put(jnp.zeros((n,), jnp.float32))
    dev.block_until_ready()
    best = None
    for _ in range(int(repeats) + 1):
        t0 = time.perf_counter()
        host = np.asarray(jax.device_get(dev))
        back = jax.device_put(host)
        back.block_until_ready()
        dt = time.perf_counter() - t0
        if best is None or dt < best:
            best = dt
    _measured_bw = (2.0 * n * 4) / max(best, 1e-9)
    return _measured_bw


# ---------------------------------------------------------------------------
# optimizer-state host offload

class ArenaOffloader:
    """Double-buffered host offload of the arena's Adam moments.

    Mirrors the grad-sync comm worker (``parallel/overlap.py``): one
    worker thread owns the transfers, so the ``offload.d2h`` /
    ``offload.h2d`` spans land on their own trace track and overlap
    the main thread's forward/backward dispatch. Per-step protocol,
    driven from ``Optimizer._apply_update``'s arena branch:

    * :meth:`collect` — before the fused apply: wait for the pending
      prefetch (exposed remainder → ``mem.offload.exposed_wait_s``)
      and rebind the slot tensors to the prefetched device arrays.
    * :meth:`page_out` — after ``arena.finish_step()``: enqueue D2H of
      the just-updated moments, drop the device references (the HBM
      saving — the split fwd/bwd executable never carries them as
      arguments), then start the H2D prefetch for the next apply.

    Only ``grp.slots`` (moment1/moment2 — 2× param bytes, the dominant
    state) page; the fp32 master ``flat`` stays resident (the forward
    reads it) and the beta-pow scalars are not worth a transfer.
    Paging is bit-exact: device_get/device_put round-trip the payload
    untouched, and checkpoints see device state again because
    ``state_dict``/``set_state_dict`` call :meth:`materialize` first.
    """

    def __init__(self):
        self._pool = None
        self._pending = None   # Future -> [(slot_tensor, device_array)]
        self.steps = 0
        self.exposed_wait_s = 0.0
        self.transfer_s = 0.0     # blocking D2H+H2D time in the worker
        self.bytes_out = 0
        self.bytes_in = 0

    def _worker(self):
        if self._pool is None:
            from concurrent.futures import ThreadPoolExecutor
            self._pool = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="offload-worker")
        return self._pool

    def collect(self, arena, count_exposed=True):
        """Wait for the in-flight page-out/prefetch and rebind the slot
        tensors to the returned device arrays. No-op when idle."""
        fut, self._pending = self._pending, None
        if fut is None:
            return
        from ..monitor import trace as _trace
        from .. import monitor as _mon
        t0 = time.perf_counter()
        with _trace.span("offload.wait"):
            prefetched = fut.result()
        dt = time.perf_counter() - t0
        if count_exposed:
            self.exposed_wait_s += dt
            if _mon.enabled():
                _mon.histogram("mem.offload.exposed_wait_s").observe(dt)
                _mon.counter("mem.offload.exposed_wait_s_total").inc(dt)
        for t, dev in prefetched:
            t.data = dev
        self.steps += 1

    def page_out(self, arena):
        """Asynchronously page the arena's slot buffers to host and
        start the H2D prefetch for the next apply."""
        if self._pending is not None:      # lag-1 safety: never stack
            self.collect(arena, count_exposed=False)
        slots = tuple(t for grp in arena.groups
                      for t in grp.slots.values())
        if not slots:
            return
        offloader = self

        def task():
            import jax
            import numpy as np
            from ..monitor import trace as _trace
            t0 = time.perf_counter()
            nbytes = 0
            hosts = []
            with _trace.span("offload.d2h", n=len(slots)):
                for t in slots:
                    h = np.asarray(jax.device_get(t.data))
                    nbytes += h.nbytes
                    hosts.append(h)
            for t, h in zip(slots, hosts):
                t.data = h        # drop the device reference: HBM freed
            with _trace.span("offload.h2d", n=len(slots),
                             bytes=nbytes):
                devs = [jax.device_put(h) for h in hosts]
                for d in devs:
                    d.block_until_ready()
            offloader.transfer_s += time.perf_counter() - t0
            offloader.bytes_out += nbytes
            offloader.bytes_in += nbytes
            return list(zip(slots, devs))

        self._pending = self._worker().submit(task)

    def materialize(self, arena):
        """Force the optimizer state device-resident (checkpoint
        save/restore slices the slot buffers; exactness requires the
        round trip to have landed)."""
        self.collect(arena, count_exposed=False)

    def shutdown(self):
        pool, self._pool = self._pool, None
        self._pending = None
        if pool is not None:
            pool.shutdown(wait=True)


def attach_offload(opt):
    """Arm optimizer-state host offload on ``opt`` (forces the flat
    arena on — offload pages the arena's flat slot buffers, nothing
    else). Returns the (possibly pre-existing) ArenaOffloader."""
    off = getattr(opt, "_offloader", None)
    if off is None:
        opt.set_flat_arena(True)
        off = ArenaOffloader()
        opt._offloader = off
    return off


def detach_offload(opt):
    """Disarm offload on ``opt``: bring any paged-out slot buffers back
    on device, stop the worker thread, and drop the offloader. The
    optimizer keeps training exactly as before — the arena never left."""
    off = getattr(opt, "_offloader", None)
    if off is None:
        return
    if getattr(opt, "_arena", None) is not None:
        off.materialize(opt._arena)
    off.shutdown()
    opt._offloader = None


# ---------------------------------------------------------------------------
# the auto-picker

_last_decision = None


def _by_class_bytes(rep):
    bc = rep.get("by_class") or {}
    act = float(bc.get("activation", 0.0)) + float(bc.get("remat", 0.0))
    opt = float(bc.get("opt_state", 0.0))
    return act, opt


def candidate_table(rep, limit=None, host_headroom=None,
                    step_flops=None, ceilings=None, link_bps=None):
    """The candidate-policy ladder with predicted peaks and step-time
    overheads, derived from one baseline (no-remat) memory report.

    Peak model (docs/performance.md §8): remat removes a fraction of
    the live-at-peak *activation* class — dots ≈ 50% (the elementwise
    intermediates between saved matmul outputs), full ≈ 90%
    (everything but the checkpointed inputs); offload removes the
    *opt_state* class entirely (the split step's fwd/bwd executable no
    longer carries it). Cost model: "full" recomputes ~one forward
    (fwd ≈ step_flops/3 of the fwd+bwd+apply 6N split) on the roofline
    flops ceiling, "dots" ~25% of a forward; offload moves 2× the
    paged bytes (D2H + H2D) over the host link — assumed hidden behind
    compute, with the un-hidden remainder gated by the smoke's
    exposed-wait check, and refused outright when the host headroom
    can't take the paged state."""
    peak = float(rep["predicted_peak_bytes"])
    act, opt = _by_class_bytes(rep)
    if ceilings is None:
        from ..monitor import profile as _prof
        ceilings = _prof.roofline_ceilings()
    fwd_s = (float(step_flops) / 3.0 / float(ceilings["peak_flops"])
             if step_flops else 0.0)
    link = link_bps if link_bps is not None else host_link_bandwidth()
    offload_s = 2.0 * opt / link
    cands = [
        {"policy": MemoryPolicy(), "name": "none",
         "predicted_peak_bytes": peak, "overhead_s": 0.0},
        {"policy": MemoryPolicy(remat="dots"), "name": "dots",
         "predicted_peak_bytes": peak - 0.5 * act,
         "overhead_s": 0.25 * fwd_s},
        {"policy": MemoryPolicy(remat="full"), "name": "full",
         "predicted_peak_bytes": peak - 0.9 * act,
         "overhead_s": fwd_s},
        {"policy": MemoryPolicy(remat="full", offload=True),
         "name": "full+offload",
         "predicted_peak_bytes": peak - 0.9 * act - opt,
         "overhead_s": fwd_s + offload_s},
    ]
    for c in cands:
        c["feasible"] = (limit is None
                         or c["predicted_peak_bytes"] <= float(limit))
        c["offload_bytes"] = opt if c["policy"].offload else 0.0
        c["host_ok"] = not (c["policy"].offload
                            and host_headroom is not None
                            and opt > host_headroom)
    return cands


def plan_memory(auto=True, label=None, hlo=None, limit=None,
                step_flops=None, link_gbps=None, record=True):
    """Pick the cheapest memory policy whose predicted peak fits.

    Consumes PR 12's predicted-peak model: simulate the captured
    baseline executable (``label`` picks a ``monitor.xla`` capture,
    default newest; ``hlo=`` simulates raw HLO text instead), build
    the candidate ladder via :func:`candidate_table`, drop candidates
    over ``limit`` (default :func:`monitor.memory.device_hbm_limit`)
    or over the host budget, pick the lowest-overhead survivor, and
    record the decision in the monitor ledger exactly like
    ``planner.plan(auto=True)`` (counters ``memory_plan.plan`` /
    ``memory_plan.auto_pick``, gauges, one ``kind="memory_plan"``
    JSONL record, :func:`last_decision`). Raises ValueError when no
    candidate fits — the planner's all-infeasible refusal, not a
    silent OOM. ``auto=False`` builds and records the table but
    returns the baseline policy regardless of fit."""
    global _last_decision
    from ..monitor import memory as _mem
    from ..monitor import xla as _xla
    rep = _mem.report(label=label, hlo=hlo, emit_records=False)
    if rep is None:
        raise ValueError(
            "plan_memory: nothing to simulate — enable the monitor and "
            "compile a baseline step first (the aot capture feeds the "
            "predicted-peak model), or pass hlo=")
    if limit is None:
        limit = _mem.device_hbm_limit()
    if step_flops is None:
        try:
            step_flops = _xla.flops(rep.get("label"))
        except Exception:
            step_flops = None
    headroom = host_headroom_bytes()
    link = (float(link_gbps) * 1e9 if link_gbps
            else host_link_bandwidth())
    cands = candidate_table(rep, limit=limit, host_headroom=headroom,
                            step_flops=step_flops, link_bps=link)
    eligible = [c for c in cands if c["feasible"] and c["host_ok"]]
    if auto:
        if not eligible:
            best = min(c["predicted_peak_bytes"] for c in cands)
            raise ValueError(
                "plan_memory: every memory policy exceeds the budget "
                f"(hbm_limit={limit}, best predicted peak={best:.0f}, "
                f"host_headroom={headroom}) — shard the model "
                "(planner.advise) or raise PADDLE_TPU_HBM_LIMIT_BYTES")
        pick = min(eligible, key=lambda c: (c["overhead_s"],
                                            c["predicted_peak_bytes"]))
    else:
        pick = cands[0]
    decision = {
        "kind": "memory_plan",
        "ts": time.time(),
        "auto": bool(auto),
        "label": rep.get("label"),
        "policy": pick["policy"],
        "picked": pick["name"],
        "policy_key": policy_key(pick["policy"]),
        "predicted_peak_bytes": pick["predicted_peak_bytes"],
        "baseline_peak_bytes": rep["predicted_peak_bytes"],
        "overhead_s": pick["overhead_s"],
        "hbm_limit_bytes": limit,
        "host_headroom_bytes": headroom,
        "host_link_bytes_per_s": link,
        "step_flops": step_flops,
        "table": [{k: v for k, v in c.items() if k != "policy"}
                  for c in cands],
    }
    _last_decision = decision
    if record:
        _record(decision)
    return decision


def _record(decision):
    from .. import monitor as _monitor
    if not _monitor.enabled():
        return
    _monitor.counter("memory_plan.plan").inc()
    if decision["auto"]:
        _monitor.counter("memory_plan.auto_pick").inc()
    _monitor.gauge("memory_plan.candidates").set(
        len(decision["table"]))
    _monitor.gauge("memory_plan.predicted_peak_bytes").set(
        decision["predicted_peak_bytes"])
    _monitor.gauge("memory_plan.overhead_s").set(
        decision["overhead_s"])
    _monitor.emit(kind="memory_plan", auto=decision["auto"],
                  picked=decision["picked"],
                  policy_key=decision["policy_key"],
                  label=decision["label"],
                  predicted_peak_bytes=decision["predicted_peak_bytes"],
                  baseline_peak_bytes=decision["baseline_peak_bytes"],
                  overhead_s=decision["overhead_s"],
                  hbm_limit_bytes=decision["hbm_limit_bytes"],
                  host_headroom_bytes=decision["host_headroom_bytes"],
                  table=decision["table"])


def last_decision():
    """The most recent plan_memory() decision dict (None before the
    first call) — same contract as planner.last_decision()."""
    return _last_decision


def reset():
    global _last_decision, _measured_bw
    _last_decision = None
    _measured_bw = None
