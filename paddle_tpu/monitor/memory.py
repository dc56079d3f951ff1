"""paddle_tpu.monitor.memory — HBM buffer liveness, peak attribution,
and OOM forensics.

``monitor.profile`` (PR 9) answers "which layer owns the flops";
this module answers the question ROADMAP item 4 calls first-class —
*which layer owns the peak HBM, and will this layout even fit?* It
walks the **scheduled** instruction stream of a captured executable's
optimized HLO (``is_scheduled=true`` — text order IS the schedule),
assigns every top-level buffer a (def, last-use) interval and a size
from its shape/dtype, and simulates occupancy over the schedule:

* ``predicted_peak_bytes`` — the simulated high-water mark, following
  XLA's own ``memory_analysis()`` accounting (arguments resident for
  the whole execution, non-aliased outputs live to the end, donated
  input/output pairs counted once via the module's
  ``input_output_alias`` map, fusion-internal temps excluded because
  only the top-level stream allocates). Reconciled against
  ``Compiled.memory_analysis()`` peak (``xla.peak_memory.<label>``)
  and the sampler's live ``mem.device.*.peak_bytes_in_use`` watermark.
* a ranked **peak-contributor ledger** — the buffers live at the peak
  instant, attributed to framework scopes through the ``profile``
  scope registry and classified ``param`` / ``activation`` /
  ``opt_state`` / ``temp``.
* a **memory-over-time curve**, exported as Chrome-trace ``"C"``
  counter events on its own track (``trace.counter``), so Perfetto
  shows predicted HBM occupancy under the span timeline.

Two loops close on this model: ``parallel.planner.advise()`` calls
:func:`device_hbm_limit` to mark over-budget layouts infeasible
(the pre-flight budget report), and the Executor/``hapi.fit`` crash
handlers call :func:`handle_oom` so every RESOURCE_EXHAUSTED leaves a
flight-recorder dump bundling this report next to the op ledger.

Cost discipline: nothing here runs until :func:`report` (or an OOM)
— the liveness model is a pure post-hoc parse of HLO text that was
captured anyway, and ``is_oom_error`` is only consulted on the crash
path. All CPU-runnable: HLO + memory_analysis need no TPU.

Usage::

    from paddle_tpu import monitor
    monitor.enable(); monitor.profile.enable()
    ... one jitted train step (aot-captured by monitor.xla) ...
    rep = monitor.memory.report()          # structured dict
    print(monitor.memory.format_table(rep))
"""
from __future__ import annotations

import os
import re
import time

from . import profile as _profile

__all__ = [
    "parse_io_alias", "liveness", "simulate", "report", "last_report",
    "last_summary", "format_table", "curve_counter_events",
    "device_hbm_limit", "is_oom_error", "handle_oom", "last_oom",
    "reset", "CLASSES",
]

CLASSES = ("param", "activation", "opt_state", "temp", "remat")

# HLO op-name markers jax.checkpoint leaves on the backward's replay
# (the primal forward keeps plain scope names — only recomputation is
# tagged): the jvp(checkpoint) transpose path and remat2's
# rematted_computation sub-scope. Buffers born under either are
# recomputed activations, not stored ones.
_REMAT_NAME_RE = re.compile(
    r"rematted_computation|remat2|jvp\(checkpoint\)|(?:^|/)checkpoint/")

# view opcodes: they alias operand storage, never allocate
_TUPLE_OPS = frozenset(("tuple",))
_GTE_OPS = frozenset(("get-tuple-element",))
_ALIAS_OPS = frozenset(("bitcast", "after-all", "optimization-barrier"))
# while writes its state in place: output aliases the operand tuple
_INPLACE_OPS = frozenset(("while",))
# no backing buffer at runtime (constants live in the executable image,
# outside the argument/output/temp accounting this model mirrors)
_NO_BUFFER_OPS = frozenset(("constant", "partition-id", "replica-id"))

_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)\s*$")
_GTE_INDEX_RE = re.compile(r"index=(\d+)")
_ALIAS_PAIR_RE = re.compile(r"\{\s*(\d+)[^}]*\}\s*:\s*\(\s*(\d+)")

_last = None            # cached last report() result
_last_oom = None        # {"ts","path","where","step","error"} of last OOM


# ---------------------------------------------------------------------------
# HLO module header: donated input/output pairs

def parse_io_alias(text):
    """The ``input_output_alias={ {out}: (param, ...), ... }`` map from
    the HloModule header line -> {output_tuple_index: param_number}.
    Empty dict when the module declares no aliasing (no donation)."""
    head = text.find("input_output_alias=")
    if head < 0:
        return {}
    brace = text.find("{", head)
    if brace < 0:
        return {}
    end = _profile._balanced(text, brace, "{", "}")
    body = text[brace + 1:end - 1]
    out = {}
    for om, pm in _ALIAS_PAIR_RE.findall(body):
        out[int(om)] = int(pm)
    return out


def _operand_name(operand):
    m = _OPERAND_NAME_RE.search(operand)
    return m.group(1) if m else None


# ---------------------------------------------------------------------------
# the liveness model

def liveness(text, scope_map=None):
    """Buffer intervals over the scheduled entry computation.

    Returns ``{"buffers": {name: row}, "schedule_len": N,
    "alias_map": {...}}`` or None when the text has no entry. Each row:
    ``size`` (bytes), ``def_idx`` / ``last_use`` (schedule indices,
    inclusive), ``space`` ("argument" / "output" / "temp"),
    ``donated`` (output written in place into a donated argument —
    contributes no bytes of its own), ``region`` / ``scope_kind``
    (profile-registry attribution, with a first-scoped-consumer
    fallback for unlabeled buffers like parameters and copies), and
    ``klass`` (param / activation / opt_state / temp).

    Only the top-level stream allocates: fusion bodies, folded
    ``to_apply`` reducers and while bodies are internal to their
    calling instruction, so their temps never appear — exactly XLA's
    buffer-assignment view. ``tuple`` / ``get-tuple-element`` /
    ``bitcast`` are views; ``while`` aliases its operand tuple in
    place."""
    scope_map = (dict(_profile._scopes) if scope_map is None
                 else dict(scope_map))
    comps, entry, _refs = _profile.parse_hlo(text)
    if entry is None:
        return None
    instrs = comps[entry]["instrs"]
    n = len(instrs)
    alias_map = parse_io_alias(text)

    buffers = {}     # name -> row
    views = {}       # name -> ("tuple", [members]) | ("gte", src, idx)
    #                          | ("alias", [srcs])
    root = None

    for i, ins in enumerate(instrs):
        op, name = ins["opcode"], ins["name"]
        if ins.get("root"):
            root = ins
        if op == "parameter":
            try:
                pnum = int(ins["operands"][0])
            except (ValueError, IndexError):
                pnum = -1
            buffers[name] = {
                "name": name, "opcode": op, "op_name": ins["op_name"],
                "size": _profile._type_bytes(ins["out_type"]),
                "def_idx": 0, "last_use": n - 1, "space": "argument",
                "pnum": pnum, "donated": False,
                "consumer_region": None, "consumer_kinds": set(),
            }
        elif op in _NO_BUFFER_OPS:
            pass
        elif op in _TUPLE_OPS:
            views[name] = ("tuple",
                           [_operand_name(o) for o in ins["operands"]])
        elif op in _GTE_OPS:
            gm = _GTE_INDEX_RE.search(ins["attrs"])
            views[name] = ("gte",
                           _operand_name(ins["operands"][0])
                           if ins["operands"] else None,
                           int(gm.group(1)) if gm else 0)
        elif op in _ALIAS_OPS or op in _INPLACE_OPS:
            views[name] = ("alias",
                           [_operand_name(o) for o in ins["operands"]])
        else:
            buffers[name] = {
                "name": name, "opcode": op, "op_name": ins["op_name"],
                "size": _profile._type_bytes(ins["out_type"]),
                "def_idx": i, "last_use": i, "space": "temp",
                "pnum": None, "donated": False,
                "consumer_region": None, "consumer_kinds": set(),
            }

    def _tuple_members(src, depth=0):
        # follow alias/while chains to a concrete tuple view's members
        while src is not None and depth < 64:
            depth += 1
            if src in buffers:
                return None
            v = views.get(src)
            if v is None:
                return None
            if v[0] == "tuple":
                return v[1]
            src = v[1][0] if (v[0] == "alias" and v[1]) else (
                v[1] if v[0] == "gte" else None)
        return None

    def _resolve(name, depth=0):
        """Concrete buffer names a reference ultimately reads."""
        if name is None or depth > 64:
            return []
        if name in buffers:
            return [name]
        v = views.get(name)
        if v is None:
            return []
        if v[0] == "tuple":
            out = []
            for m in v[1]:
                out.extend(_resolve(m, depth + 1))
            return out
        if v[0] == "gte":
            members = _tuple_members(v[1])
            if members is not None and 0 <= v[2] < len(members):
                return _resolve(members[v[2]], depth + 1)
            return _resolve(v[1], depth + 1)
        out = []
        for m in v[1]:
            out.extend(_resolve(m, depth + 1))
        return out

    # uses: every operand reference extends the underlying buffers'
    # lifetimes; the first *scoped* consumer also donates attribution
    # to unlabeled buffers (parameters, compiler-inserted copies)
    for i, ins in enumerate(instrs):
        if ins["opcode"] == "parameter":
            continue
        region, leaf = _profile._region_of(ins["op_name"], scope_map)
        kind = scope_map.get(leaf) if leaf else None
        for opnd in ins["operands"]:
            ref = _operand_name(opnd)
            if ref is None:
                continue
            for b in _resolve(ref):
                row = buffers[b]
                if i > row["last_use"] and row["space"] != "argument":
                    row["last_use"] = i
                if kind:
                    row["consumer_kinds"].add(kind)
                    if row["consumer_region"] is None and \
                            region != _profile.UNATTRIBUTED:
                        row["consumer_region"] = (region, leaf)

    # a compiler-inserted copy carries no op_name: what it feeds names it
    # (above), and it passes that name on to what it copies — the XLA of
    # jaxlib 0.9 copies donated parameters before their first use, so a
    # parameter's only direct consumer can be such a copy
    for ins in reversed(instrs):
        if ins["opcode"] != "copy" or ins["op_name"]:
            continue
        own = buffers.get(ins["name"])
        if own is None or own["consumer_region"] is None:
            continue
        for opnd in ins["operands"]:
            for b in _resolve(_operand_name(opnd)):
                row = buffers[b]
                row["consumer_kinds"] |= own["consumer_kinds"]
                if row["consumer_region"] is None:
                    row["consumer_region"] = own["consumer_region"]
    # ... and a copy that feeds only the ROOT tuple (an updated state
    # leaf on its way out) is named by the buffer it copies
    for ins in instrs:
        own = buffers.get(ins["name"])
        if ins["opcode"] != "copy" or ins["op_name"] or own is None \
                or own["consumer_region"] is not None:
            continue
        for opnd in ins["operands"]:
            for b in _resolve(_operand_name(opnd)):
                region, leaf = _profile._region_of(buffers[b]["op_name"],
                                                   scope_map)
                if region != _profile.UNATTRIBUTED:
                    own["consumer_region"] = (region, leaf)
                    break

    # outputs: ROOT tuple components live to the end of the schedule;
    # a component aliased to a donated parameter is written *in place*
    # into the argument buffer, so it contributes no bytes of its own
    if root is not None:
        if root["opcode"] in _TUPLE_OPS:
            out_refs = [_operand_name(o) for o in root["operands"]]
        else:
            out_refs = [root["name"]]
        for j, ref in enumerate(out_refs):
            for b in _resolve(ref):
                row = buffers[b]
                if j in alias_map:
                    if row["space"] != "argument":
                        row["donated"] = True
                else:
                    if row["space"] != "argument":
                        row["space"] = "output"
                    row["last_use"] = n - 1

    # attribution + class
    for row in buffers.values():
        region, leaf = _profile._region_of(row["op_name"], scope_map)
        if region == _profile.UNATTRIBUTED and row["consumer_region"]:
            region, leaf = row["consumer_region"]
        row["region"] = region
        row["scope"] = leaf
        row["scope_kind"] = scope_map.get(leaf) if leaf else None
        row["klass"] = _classify(row)
        del row["consumer_region"]
        row["consumer_kinds"] = sorted(row["consumer_kinds"])
    return {"buffers": buffers, "schedule_len": n,
            "alias_map": alias_map}


def _classify(row):
    """param / activation / opt_state / temp / remat for one buffer
    row. remat = an activation recomputed inside a jax.checkpoint
    replay — split out so a rematerialized step's by-class report stays
    honest about what is stored state vs transient recompute."""
    if row["space"] == "argument":
        # jit.to_static labels entry params "state_vals[k]"/"arrays[k]";
        # data arrays are input activations, not weights
        if row["op_name"].startswith("arrays"):
            return "activation"
        kinds = row["consumer_kinds"]
        if kinds and all(k == "optimizer" for k in kinds):
            return "opt_state"
        return "param"
    if _REMAT_NAME_RE.search(row["op_name"]):
        return "remat"
    if row["scope_kind"] == "optimizer":
        return "opt_state"
    if row["scope_kind"] in ("layer", "functional", "op"):
        return "activation"
    return "temp"


# ---------------------------------------------------------------------------
# occupancy simulation

def simulate(text, scope_map=None, top_k=10):
    """Liveness + occupancy over the schedule. Returns the full
    simulation dict (no xla/monitor coupling — pure text in, dict out):
    ``predicted_peak_bytes``, ``peak_index``, ``curve`` (occupancy per
    schedule slot), the byte split (``argument_bytes`` /
    ``output_bytes`` / ``donated_bytes`` / ``temp_peak_bytes``), the
    ranked ``contributors`` ledger (top_k live-at-peak buffers),
    ``by_class`` byte totals at peak, and ``attributed_frac`` — the
    fraction of live-at-peak bytes credited to a registered scope."""
    live = liveness(text, scope_map=scope_map)
    if live is None:
        return None
    n = live["schedule_len"]
    deltas = [0] * (n + 1)
    arg_bytes = out_bytes = donated_bytes = 0
    for row in live["buffers"].values():
        size = row["size"]
        if row["space"] == "argument":
            arg_bytes += size
        elif row["donated"]:
            donated_bytes += size
            continue
        elif row["space"] == "output":
            out_bytes += size
        if size <= 0:
            continue
        deltas[row["def_idx"]] += size
        if row["last_use"] + 1 <= n:
            deltas[row["last_use"] + 1] -= size
    curve, cur = [], 0
    for i in range(n):
        cur += deltas[i]
        curve.append(cur)
    peak = max(curve) if curve else 0
    peak_idx = curve.index(peak) if curve else 0

    contributors, live_total, attributed = [], 0, 0
    by_class = dict.fromkeys(CLASSES, 0)
    for row in live["buffers"].values():
        if row["donated"] or row["size"] <= 0:
            continue
        if not (row["def_idx"] <= peak_idx <= row["last_use"]):
            continue
        live_total += row["size"]
        by_class[row["klass"]] = by_class.get(row["klass"], 0) \
            + row["size"]
        if row["region"] != _profile.UNATTRIBUTED:
            attributed += row["size"]
        contributors.append({
            "name": row["name"], "opcode": row["opcode"],
            "bytes": row["size"], "class": row["klass"],
            "region": row["region"], "scope_kind": row["scope_kind"],
            "space": row["space"], "def_idx": row["def_idx"],
            "last_use": row["last_use"],
        })
    contributors.sort(key=lambda c: (-c["bytes"], c["name"]))
    for rank, c in enumerate(contributors, start=1):
        c["rank"] = rank
    return {
        "schedule_len": n,
        "predicted_peak_bytes": float(peak),
        "peak_index": peak_idx,
        "argument_bytes": float(arg_bytes),
        "output_bytes": float(out_bytes),
        "donated_bytes": float(donated_bytes),
        "temp_peak_bytes": float(peak - arg_bytes - out_bytes)
        if peak else 0.0,
        "curve": curve,
        "live_at_peak_bytes": float(live_total),
        "attributed_bytes": float(attributed),
        "attributed_frac": (attributed / live_total) if live_total
        else 0.0,
        "by_class": by_class,
        "contributors": contributors[:max(0, int(top_k))],
        "n_buffers": len(live["buffers"]),
        "n_donated": sum(1 for r in live["buffers"].values()
                         if r["donated"]),
    }


# ---------------------------------------------------------------------------
# the report (xla reconciliation + monitor emission)

def report(label=None, top_k=10, hlo=None, emit_records=True):
    """Build the memory report for a captured executable.

    ``label`` picks a ``monitor.xla`` capture (default: newest);
    ``hlo=`` simulates a raw HLO string instead. Adds to the pure
    simulation: ``xla_peak_bytes`` (from ``memory_analysis()``) and
    the ``reconciliation`` ratio predicted/xla, plus
    ``measured_peak_bytes`` — the live sampler watermark
    (max ``peak_bytes_in_use`` across devices, None on backends that
    expose nothing, e.g. CPU). Emits
    ``memory.predicted_peak_bytes.<label>`` /
    ``memory.attributed_frac.<label>`` gauges, one ``memory_report``
    JSONL record, and — when span tracing is live — the occupancy
    curve as Chrome ``"C"`` counter events on an ``hbm`` track.
    Returns None when nothing has been captured."""
    global _last
    from . import xla as _xla
    xla_peak = None
    if hlo is None:
        exe = _xla.executable(label)
        if exe is None:
            return None
        if label is None:
            newest = _xla.last()
            label = newest[0] if newest else None
        try:
            hlo = exe.as_text()
        except Exception:
            return None
        xla_peak = _xla.peak_memory(label)
    sim = simulate(hlo, top_k=top_k)
    if sim is None:
        return None
    measured = None
    try:
        from .step import device_memory_stats
        stats = device_memory_stats()
        peaks = [s["peak_bytes_in_use"] for s in stats.values()
                 if "peak_bytes_in_use" in s]
        measured = float(max(peaks)) if peaks else None
    except Exception:
        measured = None
    rep = dict(sim)
    rep.update({
        "kind": "memory_report",
        "ts": time.time(),
        "label": label,
        "xla_peak_bytes": xla_peak,
        "reconciliation": (sim["predicted_peak_bytes"] / xla_peak
                           if xla_peak else None),
        "measured_peak_bytes": measured,
        "hbm_limit_bytes": device_hbm_limit(),
    })
    _last = rep
    from . import emit, enabled as _mon_enabled, gauge
    from . import trace as _trace
    if emit_records and _mon_enabled():
        gauge(f"memory.predicted_peak_bytes.{label}").set(
            rep["predicted_peak_bytes"])
        gauge(f"memory.attributed_frac.{label}").set(
            rep["attributed_frac"])
        emit(kind="memory_report", label=label,
             predicted_peak_bytes=rep["predicted_peak_bytes"],
             xla_peak_bytes=xla_peak,
             reconciliation=rep["reconciliation"],
             measured_peak_bytes=measured,
             attributed_frac=rep["attributed_frac"],
             by_class=rep["by_class"],
             contributors=[
                 {"rank": c["rank"], "bytes": c["bytes"],
                  "class": c["class"], "region": c["region"]}
                 for c in rep["contributors"][:top_k]])
    if emit_records and _trace.enabled():
        for name, values, ts in curve_counter_events(rep):
            _trace.counter(name, values, ts=ts)
    return rep


def curve_counter_events(rep, max_points=512):
    """The occupancy curve as ``(name, values, ts)`` triples for
    ``trace.counter`` — one synthetic microsecond per schedule slot on
    an ``hbm.predicted[<label>]`` counter track, decimated to at most
    ``max_points`` samples (peak-preserving: the decimation keeps each
    window's max)."""
    curve = rep.get("curve") or []
    if not curve:
        return []
    label = rep.get("label") or "hlo"
    name = f"hbm.predicted[{label}]"
    n = len(curve)
    stride = max(1, (n + max_points - 1) // max_points)
    t0 = time.perf_counter()
    out = []
    for start in range(0, n, stride):
        window = curve[start:start + stride]
        out.append((name, {"bytes": max(window)},
                    t0 + start * 1e-6))
    return out


def last_report():
    """The most recent report() result (full ledger), or None."""
    return _last


def last_summary(top_k=3):
    """Compact view of the last report for /snapshot: predicted vs
    measured peak, reconciliation, and the top-k contributors."""
    rep = _last
    if rep is None:
        return None
    return {
        "label": rep["label"],
        "ts": rep["ts"],
        "predicted_peak_bytes": rep["predicted_peak_bytes"],
        "xla_peak_bytes": rep["xla_peak_bytes"],
        "reconciliation": (round(rep["reconciliation"], 4)
                           if rep["reconciliation"] else None),
        "measured_peak_bytes": rep["measured_peak_bytes"],
        "attributed_frac": round(rep["attributed_frac"], 4),
        "by_class": rep["by_class"],
        "contributors": [
            {"rank": c["rank"], "bytes": c["bytes"],
             "class": c["class"], "region": c["region"]}
            for c in rep["contributors"][:top_k]
        ],
    }


def reset():
    """Clear the cached report and the last-OOM pointer."""
    global _last, _last_oom
    _last = None
    _last_oom = None


# ---------------------------------------------------------------------------
# the device HBM budget (planner's feasibility limit)

# per-chip HBM capacity (GiB) by jax device_kind substring — the
# budget line planner.advise() draws; override with
# PADDLE_TPU_HBM_LIMIT_BYTES (bytes) or PADDLE_TPU_HBM_GB
_HBM_CAPACITY_GIB = (
    ("TPU v6", 32.0),
    ("TPU v5p", 95.0),
    ("TPU v5 lite", 16.0),
    ("TPU v5e", 16.0),
    ("TPU v4", 32.0),
    ("TPU v3", 16.0),
    ("TPU v2", 8.0),
)


def device_hbm_limit(device_kind=None):
    """Per-device HBM budget in bytes, or None when unknowable.
    Resolution order: $PADDLE_TPU_HBM_LIMIT_BYTES, $PADDLE_TPU_HBM_GB,
    the backend's live ``bytes_limit``, then the capacity table by
    device kind (CPU stays None — no budget means no infeasibility
    verdicts, never an invented one)."""
    env = os.environ.get("PADDLE_TPU_HBM_LIMIT_BYTES")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    env = os.environ.get("PADDLE_TPU_HBM_GB")
    if env:
        try:
            return float(env) * (1 << 30)
        except ValueError:
            pass
    kind = device_kind
    if kind is None:
        try:
            from .step import device_memory_stats
            limits = [s["bytes_limit"]
                      for s in device_memory_stats().values()
                      if "bytes_limit" in s]
            if limits:
                return float(max(limits))
        except Exception:
            pass
        try:
            import jax
            kind = str(getattr(jax.local_devices()[0],
                               "device_kind", ""))
        except Exception:
            kind = ""
    kind = str(kind)
    for tag, gib in _HBM_CAPACITY_GIB:
        if tag in kind:
            return gib * (1 << 30)
    return None


# ---------------------------------------------------------------------------
# OOM forensics

_OOM_RE = re.compile(
    r"RESOURCE[ _]?EXHAUSTED|out of memory|\bOOM\b|"
    r"[Aa]llocation .* exceeds|failed to allocate", re.IGNORECASE)


def is_oom_error(exc):
    """True when an exception (or anything in its cause/context chain)
    is OOM-shaped: XLA's RESOURCE_EXHAUSTED, an allocator "out of
    memory", or Python's MemoryError."""
    seen = set()
    while exc is not None and id(exc) not in seen:
        seen.add(id(exc))
        if isinstance(exc, MemoryError):
            return True
        try:
            if _OOM_RE.search(str(exc)):
                return True
        except Exception:
            pass
        exc = getattr(exc, "__cause__", None) or \
            getattr(exc, "__context__", None)
    return False


def handle_oom(exc, where, step=None):
    """The crash-path hook Executor.run / hapi.fit / jit call on any
    exception: when ``exc`` is OOM-shaped, build (or reuse) the memory
    report and fire ``flight_record("oom")`` so the dump bundles the
    contributor ledger next to the op ledger + HLO. Returns the flight
    directory, or None (not an OOM, rate-capped, or anything failed —
    forensics must never add a second crash)."""
    global _last_oom
    if not is_oom_error(exc):
        return None
    try:
        if _last is None:
            report(emit_records=False)
    except Exception:
        pass
    try:
        from . import trace as _trace
        extra = {"where": str(where), "error": str(exc)[:500]}
        summary = last_summary()
        if summary:
            extra["memory"] = summary
        path = _trace.flight_record("oom", step=step, extra=extra)
        _last_oom = {"ts": time.time(), "path": path,
                     "where": str(where), "step": step,
                     "error": str(exc)[:200]}
        from . import counter, enabled as _mon_enabled
        if _mon_enabled():
            counter("memory.oom").inc()
        return path
    except Exception:
        return None


def last_oom():
    """{"ts", "path", "where", "step", "error"} of the most recent
    OOM this process handled, or None — /snapshot's pointer."""
    return _last_oom


# ---------------------------------------------------------------------------
# human-readable table

def _fmt_bytes(v):
    if v is None:
        return "n/a"
    for unit, scale in (("GiB", 1 << 30), ("MiB", 1 << 20),
                        ("KiB", 1 << 10)):
        if abs(v) >= scale:
            return f"{v / scale:.2f}{unit}"
    return f"{v:.0f}B"


def format_table(rep, top_k=10):
    """Human-readable peak-contributor ledger for a report() dict."""
    if not rep:
        return "memory: no captured executable"
    lines = [
        f"memory: {rep.get('label') or '<hlo>'}  "
        f"predicted peak {_fmt_bytes(rep['predicted_peak_bytes'])}"
        + (f"  (xla {_fmt_bytes(rep['xla_peak_bytes'])}, "
           f"recon {rep['reconciliation']:.3f})"
           if rep.get("xla_peak_bytes") else "")
        + (f"  measured {_fmt_bytes(rep['measured_peak_bytes'])}"
           if rep.get("measured_peak_bytes") else ""),
        f"  live at peak {_fmt_bytes(rep['live_at_peak_bytes'])} "
        f"(attributed {rep['attributed_frac']:.1%})  "
        + "  ".join(f"{k}={_fmt_bytes(v)}"
                    for k, v in rep["by_class"].items() if v),
        "",
        f"  {'#':>2} {'bytes':>10} {'class':<11} {'space':<9} "
        f"{'region':<40} {'live':<13}",
    ]
    for c in rep["contributors"][:top_k]:
        lines.append(
            f"  {c['rank']:>2} {_fmt_bytes(c['bytes']):>10} "
            f"{c['class']:<11} {c['space']:<9} {c['region'][:40]:<40} "
            f"[{c['def_idx']},{c['last_use']}]")
    return "\n".join(lines)
