"""paddle_tpu.parallel.planner — profile-guided GSPMD auto-sharding.

One planner behind every parallelism surface. Two halves:

**Layout half** — :class:`MeshPlan`: an ordered tuple of
``(regex, PartitionSpec)`` rules matched against parameter names
(first match wins, ``re.search`` semantics; scalars are always
replicated; unmatched leaves take the plan's ``default`` spec). The
plan annotates the WHOLE param/optimizer/grad-accumulator pytree —
``spec_for`` / ``annotate`` / ``place`` / ``as_spec_fn`` — and is the
single object threaded through ``hapi.Model.fit(mesh_plan=)``,
``Executor.run`` / ``train_from_dataset``, ``DataParallel``,
``MegatronConfig.mesh_plan`` and ``jit.to_static(plan=)``. Its
``plan_key()`` (mesh signature + rule-set hash) joins every executable
cache key so switching plans can never silently reuse a stale
executable. Non-divisible dims degrade through
``layout.adapt_spec`` — warned once, counted in ``layout.degraded``,
and visible to the advisor as a penalty (a degraded param's work
replicates instead of dividing).

**Advisor half** — closes the loop with measurement:
``score()`` estimates a candidate layout's step time from the roofline
model ``monitor.profile`` uses for its per-region ledger
(``max(flops/peak_flops, bytes/hbm_bw)``) plus a comm model priced in
the same wire-bytes currency as the ``comm.*`` series
(``overlap.wire_bytes`` per collective × ring factor ÷ link
bandwidth, measurable via :func:`measure_link_bandwidth`).
``advise()`` ranks candidate meshes (deterministic, tie-broken by
degradation then sizes, so the table is rank-stable), ``plan(auto=True)``
picks the winner, and the decision lands in the monitor ledger
(``planner.*`` counters/gauges, a ``kind="planner"`` JSONL record
cross-linked to the current top hotspot, and a ``planner`` block in
``/snapshot`` via :func:`last_decision`).

Cost-model honesty notes (all documented approximations, good enough
to ORDER layouts, not to predict absolute times):

* compute/memory: per-device flops and HBM bytes divide by the axes
  that split them; vocabulary logits replicate over tp; degraded
  params don't divide at all.
* comm: dp grad sync is a ring all-reduce (``2·(n−1)/n`` of the wire
  payload per rank); tp activation collectives are the Megatron f/g
  psum pairs, two per block direction; ppermute rings count one hop
  payload per step.
"""
from __future__ import annotations

import hashlib
import json
import re
import time

import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from .layout import adapt_spec, mesh_signature, spec_to_lists
from . import collective as _coll

__all__ = [
    "MeshPlan", "MEGATRON_RULES", "TRANSFORMER_RULES", "resolve",
    "candidate_sizes", "megatron_candidate_stats", "stats_from_profile",
    "score", "advise", "plan", "measure_link_bandwidth",
    "link_bandwidth", "link_is_assumed", "last_decision",
]


# ---------------------------------------------------------------------------
# canonical rule sets

# Reproduces parallel.megatron.init_params' hand specs bit-identically
# (the plan_smoke gate): qkv/ffn1 column-split over tp (qkv via its
# explicit heads axis), attn_out/ffn2 row-split, stages stacked over pp,
# experts over ep, everything else replicated.
MEGATRON_RULES = (
    (r"^qkv_w$", P("pp", None, None, None, "tp", None)),
    (r"^qkv_b$", P("pp", None, None, "tp", None)),
    (r"^attn_out_w$", P("pp", None, "tp", None, None)),
    (r"^ffn1_w$", P("pp", None, None, "tp")),
    (r"^ffn1_b$", P("pp", None, "tp")),
    (r"^ffn2_w$", P("pp", None, "tp", None)),
    (r"^moe_w[12]$", P("ep", None, None, None)),
    (r"^(ln[12]_[wb]|attn_out_b|ffn2_b)$", P("pp", None, None)),
    (r"^(embed|pos|lnf_[wb]|moe_router)$", P()),
)

# Generic transformer-shaped nn.Layer trees (zoo BERT/Transformer
# naming, the same column/row split fleet.megatron_param_spec applies
# imperatively — expressed here as data so plans hash and diff).
TRANSFORMER_RULES = (
    (r"(qkv|q_proj|k_proj|v_proj|kv_proj|ffn1|fc1|linear1|intermediate)"
     r"[^.]*\.weight$", P(None, "tp")),
    (r"(qkv|q_proj|k_proj|v_proj|kv_proj|ffn1|fc1|linear1|intermediate)"
     r"[^.]*\.bias$", P("tp")),
    (r"(out|o_proj|out_proj|ffn2|fc2|linear2|output)[^.]*\.weight$",
     P("tp", None)),
)


def _as_spec(s):
    """Accept a PartitionSpec, a spec_to_lists form, or None."""
    if s is None:
        return P()
    if isinstance(s, P):
        return s
    from .layout import spec_from_lists
    return spec_from_lists(list(s))


# ---------------------------------------------------------------------------
# MeshPlan

class MeshPlan:
    """Ordered regex→PartitionSpec rules bound to a mesh.

    rules      — iterable of ``(pattern, spec)``; spec may be a
                 PartitionSpec or its spec_to_lists form. First match
                 (``re.search``) wins.
    mesh       — jax Mesh; defaults to ``collective.get_mesh()`` or a
                 pure-dp mesh over every visible device.
    default    — spec for unmatched non-scalar leaves (replicated).
    data_axes  — mesh axes that shard the batch dim of *inputs*
                 (``data_spec`` / ``shard_input``) and carry grad sync.
    """

    def __init__(self, rules, mesh=None, default=P(), data_axes=("dp",),
                 name="plan"):
        if mesh is None:
            mesh = _coll.get_mesh()
        if mesh is None:
            # pure-dp fallback over every visible device — built
            # directly (NOT via collective.make_mesh) so constructing a
            # plan never mutates the process-global registered mesh
            from jax.sharding import Mesh
            devs = np.asarray(jax.devices())
            mesh = Mesh(devs.reshape((devs.size,)), ("dp",))
        self.mesh = mesh
        self.name = name
        self.default = _as_spec(default)
        self.data_axes = tuple(data_axes)
        self.sizes = {str(n): int(s) for n, s in mesh.shape.items()}
        self.rules = tuple((str(pat), _as_spec(spec))
                           for pat, spec in (rules or ()))
        self._compiled = tuple((re.compile(pat), spec)
                               for pat, spec in self.rules)
        self._validate()
        # degradation ledger for the advisor: name -> elems replicated
        # instead of sharded (filled lazily as spec_for runs)
        self.degraded = {}

    # -- validation ---------------------------------------------------
    def _axes_of(self, spec):
        out = []
        for e in tuple(spec):
            if e is None:
                continue
            out.extend(e if isinstance(e, (tuple, list)) else (e,))
        return out

    def _validate(self):
        known = set(self.sizes)
        for pat, spec in self.rules + (("<default>", self.default),):
            for ax in self._axes_of(spec):
                if str(ax) not in known:
                    raise ValueError(
                        f"mesh_plan rule {pat!r} shards over axis "
                        f"{ax!r}, but the mesh only has axes "
                        f"{sorted(known)}")
        for ax in self.data_axes:
            if ax not in known:
                raise ValueError(
                    f"mesh_plan data axis {ax!r} not on mesh "
                    f"(axes {sorted(known)})")

    # -- rule matching ------------------------------------------------
    def match(self, name):
        """The raw rule spec for `name` (no shape adaptation), or the
        default. Scalars are handled by spec_for."""
        for rx, spec in self._compiled:
            if rx.search(name):
                return spec
        return self.default

    def spec_for(self, name, shape):
        """PartitionSpec for one leaf: first-match rule, trimmed and
        divisibility-adapted to `shape` (degradations warn once and
        count in layout.degraded + this plan's ledger)."""
        shape = tuple(shape or ())
        if len(shape) == 0:
            return P()
        lists = spec_to_lists(self.match(name), len(shape))
        spec, changed = adapt_spec(lists, shape, self.mesh, name=name)
        if changed:
            self.degraded[name] = int(np.prod(shape)) if shape else 1
        entries = list(tuple(spec))
        while entries and entries[-1] is None:  # canonical: P(None,)==P()
            entries.pop()
        return P(*entries)

    def annotate(self, named_shapes):
        """{name: shape-or-array} → {name: PartitionSpec} for the whole
        tree (params, optimizer slots, grad accumulators alike — slots
        share their param's name prefix so the same rules bind)."""
        out = {}
        for k, v in named_shapes.items():
            shape = v if isinstance(v, (tuple, list)) else np.shape(
                getattr(v, "data", v))
            out[k] = self.spec_for(k, shape)
        return out

    def as_spec_fn(self):
        """(name, shape) → spec callable, for fleet.shard_model."""
        return lambda name, shape: self.spec_for(name, shape)

    def place(self, name, value):
        """device_put one leaf under its planned spec (Tensor-aware)."""
        arr = getattr(value, "data", value)
        spec = self.spec_for(name, np.shape(arr))
        placed = jax.device_put(arr, NamedSharding(self.mesh, spec))
        if hasattr(value, "data"):
            value.data = placed
            return value
        return placed

    def place_model(self, model):
        """Shard every parameter (and replicate every buffer) of an
        nn.Layer tree in place. Unlike fleet.shard_model, an applied
        plan is authoritative: existing placements are overridden."""
        for name, prm in model.named_parameters():
            self.place(name, prm)
        for name, buf in model.named_buffers():
            if hasattr(buf, "data"):
                buf.data = jax.device_put(
                    buf.data, NamedSharding(self.mesh, P()))
        return model

    def place_optimizer(self, optimizer):
        """Place optimizer accumulator slots exactly like their params
        (call after place_model so params carry their planned
        sharding). Shape-matched slots inherit the param's sharding;
        scalar state (beta powers, step counts) is left alone."""
        params = list(getattr(optimizer, "_parameter_list", None) or [])
        acc = getattr(optimizer, "_accumulators", None) or {}
        for prm in params:
            arr = getattr(prm, "data", prm)
            sh = getattr(arr, "sharding", None)
            if sh is None:
                continue
            for _slot, t in acc.get(id(prm), {}).items():
                tarr = getattr(t, "data", None)
                if tarr is not None and np.shape(tarr) == np.shape(arr):
                    t.data = jax.device_put(tarr, sh)
        return optimizer

    # -- input/batch layout -------------------------------------------
    def dp_size(self):
        return int(np.prod([self.sizes.get(a, 1) for a in self.data_axes]))

    def data_spec(self, ndim):
        """Batch-dim sharding for inputs: leading dim over the data
        axes (those actually >1), rest replicated."""
        axes = tuple(a for a in self.data_axes if self.sizes.get(a, 1) > 1)
        if ndim == 0 or not axes:
            return P()
        lead = axes[0] if len(axes) == 1 else axes
        return P(*((lead,) + (None,) * (ndim - 1)))

    def shard_input(self, arr):
        """Place one input batch: leading dim split over the data axes
        when divisible, replicated otherwise (never an invalid layout)."""
        shape = np.shape(arr)
        dp = self.dp_size()
        if len(shape) == 0 or dp <= 1:
            return jax.device_put(arr, NamedSharding(self.mesh, P()))
        if shape[0] % dp == 0:
            spec = self.data_spec(len(shape))
        else:
            spec = P()
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    # -- arena / grad-sync contract -----------------------------------
    def arena_compatible(self, named_shapes):
        """The flat ParamArena packs leaves into ONE replicated buffer,
        so every planned leaf must be replicated on every axis of size
        > 1. Returns the first offending (name, spec) or None."""
        for k, v in named_shapes.items():
            shape = v if isinstance(v, (tuple, list)) else np.shape(
                getattr(v, "data", v))
            spec = self.spec_for(k, shape)
            for ax in self._axes_of(spec):
                if self.sizes.get(str(ax), 1) > 1:
                    return k, spec
        return None

    def grad_axis(self):
        """The axis grad sync reduces over (first data axis of size>1)."""
        for a in self.data_axes:
            if self.sizes.get(a, 1) > 1:
                return a
        return self.data_axes[0] if self.data_axes else "dp"

    # -- identity / cache keys ----------------------------------------
    def signature(self):
        """JSON-able identity: mesh topology + canonical rule set."""
        sig = dict(mesh_signature(self.mesh))
        # axis ORDER changes device placement, and json.dumps(sort_keys)
        # would erase it from the axes dict — record it explicitly
        sig["axis_order"] = list(self.sizes)
        return {
            "mesh": sig,
            "rules": [[pat, spec_to_lists(spec, len(tuple(spec)))]
                      for pat, spec in self.rules],
            "default": spec_to_lists(self.default,
                                     len(tuple(self.default))),
            "data_axes": list(self.data_axes),
        }

    def plan_key(self):
        """Short stable string for executable cache keys: switching the
        mesh OR the rule set changes it, so no stale reuse."""
        blob = json.dumps(self.signature(), sort_keys=True)
        h = hashlib.sha1(blob.encode()).hexdigest()[:12]
        axes = "x".join(f"{a}{s}" for a, s in sorted(self.sizes.items())
                        if s > 1) or "1dev"
        return f"plan:{axes}:{h}"

    def __repr__(self):
        return (f"MeshPlan({self.name!r}, {len(self.rules)} rules, "
                f"mesh={self.sizes}, key={self.plan_key()})")


def resolve(mesh_plan, mesh=None, default=P(), data_axes=("dp",), **auto_kw):
    """Coerce the user-facing ``mesh_plan=`` knob into a MeshPlan:
    None → None, MeshPlan → itself, "auto" → plan(auto=True),
    rule iterable → MeshPlan(rules)."""
    if mesh_plan is None:
        return None
    if isinstance(mesh_plan, MeshPlan):
        return mesh_plan
    if isinstance(mesh_plan, str):
        if mesh_plan == "auto":
            return plan(auto=True, mesh=mesh, **auto_kw)
        raise ValueError(f"mesh_plan string must be 'auto', "
                         f"got {mesh_plan!r}")
    return MeshPlan(mesh_plan, mesh=mesh, default=default,
                    data_axes=data_axes)


# ---------------------------------------------------------------------------
# advisor: candidate enumeration, cost model, ranking

def candidate_sizes(n_devices, axes=("dp", "tp")):
    """All complete factorizations of `n_devices` over `axes` (every
    device used; order = axes order). 8 devices over (dp, tp) →
    [{'dp': 8, 'tp': 1}, {'dp': 4, 'tp': 2}, {'dp': 2, 'tp': 4},
    {'dp': 1, 'tp': 8}]."""
    axes = tuple(axes)
    out = []

    def rec(i, rest, acc):
        if i == len(axes) - 1:
            out.append(dict(acc, **{axes[i]: rest}))
            return
        for d in range(1, rest + 1):
            if rest % d == 0:
                rec(i + 1, rest // d, dict(acc, **{axes[i]: d}))

    if n_devices < 1:
        return []
    rec(0, int(n_devices), {})
    return out


def link_bandwidth(link_gbps=None, ceilings=None):
    """Interconnect bandwidth (bytes/s) for the comm model. Priority:
    explicit arg → PADDLE_TPU_LINK_GBPS env → an ASSUMED per-platform
    constant (90 GB/s for anything called ``tpu``, 8 GB/s for the CPU
    mesh's host memcpys) that no run has measured —
    :func:`link_is_assumed` says which, and every report of a number
    priced with it carries that flag. :func:`measure_link_bandwidth`
    gives the measured figure."""
    import os
    if link_gbps is not None:
        return float(link_gbps) * 1e9
    env = os.environ.get("PADDLE_TPU_LINK_GBPS")
    if env:
        return float(env) * 1e9
    return 90e9 if jax.devices()[0].platform == "tpu" else 8e9


def link_is_assumed(link_gbps=None):
    """True when :func:`link_bandwidth` falls to its assumed constant."""
    import os
    return link_gbps is None and not os.environ.get("PADDLE_TPU_LINK_GBPS")


def measure_link_bandwidth(mesh, axis, n_elems=1 << 22, repeats=3):
    """Measured link bandwidth: time a jitted psum of `n_elems` f32 over
    `axis` and divide the ring wire bytes by the best wall time. Returns
    bytes/s, or None when the axis has size 1 (nothing on the wire)."""
    sizes = {str(n): int(s) for n, s in mesh.shape.items()}
    n = sizes.get(axis, 1)
    if n <= 1:
        return None
    spec = P(axis)
    x = jax.device_put(np.ones((n_elems,), "f4"),
                       NamedSharding(mesh, spec))

    def dev(v):
        from jax import lax
        return lax.psum(v, axis)

    f = jax.jit(jax.shard_map(dev, mesh=mesh, in_specs=(spec,),
                              out_specs=spec, check_vma=False))
    f(x).block_until_ready()  # compile
    best = float("inf")
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        f(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    wire = 2.0 * (n - 1) / n * 4.0 * n_elems
    return wire / max(best, 1e-9)


def _ring_factor(n):
    return 2.0 * (n - 1) / n if n > 1 else 0.0


def score(stats, ceilings=None, link_gbps=None):
    """Per-layout step-time estimate from per-DEVICE stats:
    ``{"flops", "hbm_bytes", "comm": [(axis, payload_bytes, n_ranks)]}``
    → ``{"compute_s", "hbm_s", "comm_s", "pred_step_s", "bound"}``.
    Same roofline as monitor.profile (max of compute/memory ceilings),
    comm serialized on top (the planner scores what XLA may NOT
    overlap — the pessimistic bound orders layouts conservatively)."""
    if ceilings is None:
        from ..monitor import profile as _prof
        ceilings = _prof.roofline_ceilings()
    peak = float(ceilings["peak_flops"])
    hbm = float(ceilings["hbm_bytes_per_sec"])
    link = link_bandwidth(link_gbps)
    compute_s = float(stats.get("flops", 0)) / peak
    hbm_s = float(stats.get("hbm_bytes", 0)) / hbm
    comm_s = 0.0
    for _axis, payload, n in stats.get("comm", ()):
        comm_s += float(payload) * _ring_factor(int(n)) / link
    roof = max(compute_s, hbm_s)
    return {
        "compute_s": compute_s, "hbm_s": hbm_s, "comm_s": comm_s,
        "pred_step_s": roof + comm_s,
        "bound": ("comm" if comm_s > roof else
                  "compute" if compute_s >= hbm_s else "memory"),
        "link_bytes_per_s": link,
        "link_assumed": link_is_assumed(link_gbps),
    }


def megatron_candidate_stats(cfg, sizes, global_batch=None):
    """Analytic per-device stats for one MegatronConfig on one mesh
    factorization — the advisor input when there is no profile yet.
    `global_batch` is candidate-independent (defaults to
    cfg.microbatch, read as the GLOBAL batch so candidates stay
    comparable). pp>1 changes the model itself in this trainer
    (stage-stacked params), so candidates should vary dp/tp/sp only."""
    full = {"dp": 1, "pp": 1, "tp": 1, "sp": 1, "ep": 1}
    full.update(sizes)
    dp, tp, sp = full["dp"], full["tp"], full["sp"]
    h, V = cfg.hidden, cfg.vocab_size
    ffn = h * cfg.ffn_mult
    L = cfg.layers_per_stage * full["pp"]
    B = int(global_batch if global_batch is not None else cfg.microbatch)
    tokens_g = cfg.n_micro * B * cfg.seq_len
    tokens_dev = tokens_g / max(dp * sp, 1)

    # tp divisibility: heads carry qkv/attn_out, ffn carries ffn1/ffn2.
    # A non-divisible split degrades to replicated — full per-device
    # work and full-size grads (the layout.degraded penalty, priced in).
    heads_split = tp if cfg.n_heads % tp == 0 else 1
    ffn_split = tp if ffn % tp == 0 else 1
    attn_mm = L * 4 * h * h            # qkv (3h·h) + attn_out (h·h)
    ffn_mm = L * 2 * h * ffn           # ffn1 + ffn2
    embed_mm = V * h                   # logits matmul, replicated on tp
    mm_local = (attn_mm / heads_split + ffn_mm / ffn_split + embed_mm)
    flops = 6.0 * tokens_dev * mm_local
    # attention scores/context: 4·tokens·s_ctx·h fwd, ×3 with backward
    flops += 12.0 * tokens_dev * cfg.seq_len * h / heads_split
    # HBM bytes: every matmul operand + activation streamed ~3× (fwd,
    # grad, residual re-read) at f32
    param_local = (attn_mm / heads_split + ffn_mm / ffn_split
                   + embed_mm + cfg.seq_len * h)
    hbm = 4.0 * (3.0 * param_local + 12.0 * tokens_dev * h * L
                 / max(1, 1))  # activations don't split over tp (f/g)
    hbm = float(hbm)

    comm = []
    # dp grad sync: replicated params at full size (embed/pos/lns/
    # biases + any degraded split) + sharded locals, wire-priced in the
    # grad_sync mode's format
    from .overlap import wire_bytes
    grad_elems = (attn_mm / heads_split + ffn_mm / ffn_split
                  + embed_mm + cfg.seq_len * h + 10 * L * h)
    mode = cfg.grad_sync
    if getattr(cfg, "quantized_grad_allreduce", False) and mode == "exact":
        mode = "quantized"
    if dp > 1:
        comm.append(("dp", float(wire_bytes(int(grad_elems), mode,
                                            bits=cfg.grad_bits,
                                            n_ranks=dp)), dp))
    # tp activation psums: f/g pair per block sub-layer → 2 fwd + 2 bwd
    # psums per block, each tokens_dev·h f32
    if tp > 1:
        comm.append(("tp", 4.0 * L * tokens_dev * h * 4.0, tp))
    # sp ring attention: k,v ride the ring once per block per direction
    if sp > 1:
        comm.append(("sp", 4.0 * L * tokens_dev * h * 4.0
                     / max(heads_split, 1), sp))
    degraded = (heads_split == 1 and tp > 1) or (ffn_split == 1 and tp > 1)

    # predicted peak HBM residency per device (the pre-flight budget):
    # training state = params + grads + 2 Adam slots (4× param bytes),
    # plus the backward's saved activations (residual + ffn streams per
    # block) and the replicated logits buffer — f32 throughout. An
    # ordering model, same honesty contract as the flops/bytes halves.
    state_elems = 4.0 * param_local
    act_elems = L * tokens_dev * (2.0 * h + ffn / ffn_split)
    logits_elems = tokens_dev * V
    peak_hbm = 4.0 * (state_elems + act_elems + logits_elems)
    return {"flops": float(flops), "hbm_bytes": hbm, "comm": comm,
            "degraded_frac": 1.0 if degraded else 0.0,
            "peak_hbm_bytes": float(peak_hbm),
            # decomposition for the memory-policy advisory columns:
            # activations are what remat removes, the two Adam slots
            # (half the training state) are what offload removes
            "peak_act_bytes": 4.0 * float(act_elems),
            "peak_opt_bytes": 4.0 * float(2.0 * param_local)}


def stats_from_profile(sizes, report=None, param_elems=0,
                       grad_mode="exact", grad_bits=8,
                       data_axes=("dp",), model_axes=("tp",)):
    """Advisor input from the measured roofline ledger: take
    monitor.profile's attributed per-region flops/bytes (captured on
    the CURRENT layout, totalled) and rescale to a candidate mesh —
    compute/memory divide across all axes, grad traffic rides the data
    axes at ``param_elems / model-split`` wire bytes."""
    if report is None:
        from ..monitor import profile as _prof
        report = _prof.last_report()
    if not report:
        raise ValueError(
            "stats_from_profile needs a monitor.profile report — run a "
            "profiled step first (monitor.profile.enable()) or pass "
            "report=")
    flops = sum(float(r.get("flops", 0)) for r in report["regions"])
    nbytes = sum(float(r.get("bytes", 0)) for r in report["regions"])
    n = int(np.prod([max(1, int(v)) for v in sizes.values()]))
    model_split = int(np.prod([max(1, int(sizes.get(a, 1)))
                               for a in model_axes]))
    dp = int(np.prod([max(1, int(sizes.get(a, 1))) for a in data_axes]))
    comm = []
    if dp > 1 and param_elems:
        from .overlap import wire_bytes
        comm.append((data_axes[0],
                     float(wire_bytes(int(param_elems // model_split),
                                      grad_mode, bits=grad_bits,
                                      n_ranks=dp)), dp))
    # peak residency from the measured liveness model when one exists:
    # state bytes (params/opt slots) divide over the model axes, the
    # activation/temp working set over the data axes
    peak_hbm = act_bytes = opt_bytes = None
    try:
        from ..monitor import memory as _mem
        mrep = _mem.last_report()
        if mrep:
            bc = mrep.get("by_class", {})
            state = float(bc.get("param", 0) + bc.get("opt_state", 0))
            work = float(bc.get("activation", 0) + bc.get("remat", 0)
                         + bc.get("temp", 0))
            peak_hbm = state / model_split + work / max(dp, 1)
            act_bytes = work / max(dp, 1)
            opt_bytes = float(bc.get("opt_state", 0)) / model_split
    except Exception:
        peak_hbm = None
    return {"flops": flops / n, "hbm_bytes": nbytes / n, "comm": comm,
            "degraded_frac": 0.0, "peak_hbm_bytes": peak_hbm,
            "peak_act_bytes": act_bytes, "peak_opt_bytes": opt_bytes}


def advise(n_devices=None, cfg=None, candidates=None, axes=("dp", "tp"),
           global_batch=None, report=None, param_elems=0,
           ceilings=None, link_gbps=None, timeshared=None,
           hbm_limit=None):
    """Ranked layout table, best first. Each row:
    ``{rank, sizes, pred_step_s, compute_s, hbm_s, comm_s, bound,
    degraded_frac, peak_hbm_bytes, feasible, remat, offload,
    mem_overhead_s}`` — the last three are ADVISORY memory-policy
    columns (the cheapest memory_plan ladder rung that would fit the
    candidate under the HBM budget and its predicted overhead; "none"/
    False/0.0 when it already fits). Deterministic: ties
    break on degradation then on the sizes dict, so repeated calls are
    rank-stable.

    The pre-flight HBM budget (ROADMAP item 4): each candidate carries
    its predicted per-device peak residency, and a candidate whose peak
    exceeds ``hbm_limit`` (default: ``monitor.memory.device_hbm_limit()``
    — env override, live ``bytes_limit``, or the device-kind capacity
    table) is marked ``feasible: False`` and ranked BELOW every feasible
    layout regardless of its predicted step time — a layout that OOMs
    has no step time. With no limit (CPU, unknown device) and no
    override, everything stays feasible: no invented verdicts.

    ``timeshared`` (default: auto-true on CPU): the "devices" are
    virtual shards of one host, so per-device work does NOT run
    concurrently — wall clock follows TOTAL work. Stats are scaled by
    the device count and priced at honest host throughput
    ($PADDLE_TPU_HOST_GFLOPS, default 10) instead of the assumed-TPU
    ceilings, so a CPU rehearsal ranks layouts the way the CPU actually
    runs them (the plan_smoke A/B gate). On real TPU meshes this is
    off and the per-device roofline applies unchanged."""
    if n_devices is None:
        n_devices = len(jax.devices())
    if candidates is None:
        candidates = candidate_sizes(n_devices, axes)
    if not candidates:
        return []
    if timeshared is None:
        try:
            timeshared = jax.devices()[0].platform == "cpu"
        except Exception:
            timeshared = False
    if timeshared and ceilings is None:
        import os
        gf = float(os.environ.get("PADDLE_TPU_HOST_GFLOPS", "10"))
        ceilings = {"peak_flops": gf * 1e9,
                    "hbm_bytes_per_sec": 2.0 * gf * 1e9,
                    "device_kind": "timeshared-host", "assumed": True}
    if hbm_limit is None:
        try:
            from ..monitor import memory as _mem
            hbm_limit = _mem.device_hbm_limit()
        except Exception:
            hbm_limit = None
    rows = []
    for sizes in candidates:
        if cfg is not None:
            stats = megatron_candidate_stats(cfg, sizes,
                                             global_batch=global_batch)
        else:
            stats = stats_from_profile(sizes, report=report,
                                       param_elems=param_elems)
        if timeshared:
            n = int(np.prod([max(1, int(v)) for v in sizes.values()]))
            stats = dict(stats, flops=stats["flops"] * n,
                         hbm_bytes=stats["hbm_bytes"] * n)
        row = score(stats, ceilings=ceilings, link_gbps=link_gbps)
        row["sizes"] = dict(sizes)
        row["degraded_frac"] = float(stats.get("degraded_frac", 0.0))
        peak = stats.get("peak_hbm_bytes")
        row["peak_hbm_bytes"] = (float(peak) if peak is not None
                                 else None)
        row["hbm_limit_bytes"] = hbm_limit
        row["feasible"] = not (hbm_limit is not None
                               and peak is not None
                               and peak > hbm_limit)
        row["remat"], row["offload"], row["mem_overhead_s"] = \
            _mem_advice(row, stats, hbm_limit)
        rows.append(row)
    rows.sort(key=lambda r: (0 if r["feasible"] else 1,
                             round(r["pred_step_s"], 15),
                             r["degraded_frac"],
                             json.dumps(r["sizes"], sort_keys=True)))
    for i, r in enumerate(rows):
        r["rank"] = i + 1
    return rows


def _mem_advice(row, stats, hbm_limit):
    """Advisory memory-policy columns for an advise() row: the cheapest
    memory_plan ladder rung (none → dots-remat → full-remat → +offload)
    that would bring this candidate's predicted peak under the budget,
    plus its predicted step-time overhead. Purely informational —
    ``feasible`` and the ranking still describe the layout AS-IS;
    enacting the suggestion is fit(memory=)/plan_memory()'s job."""
    peak = row.get("peak_hbm_bytes")
    if peak is None or hbm_limit is None or peak <= hbm_limit:
        return "none", False, 0.0
    act = float(stats.get("peak_act_bytes") or 0.0)
    opt = float(stats.get("peak_opt_bytes") or 0.0)
    # fwd ≈ 1/3 of the fwd+bwd flop time already priced into the row
    fwd_s = float(row.get("compute_s", 0.0)) / 3.0
    from ..memory_plan import host_link_bandwidth
    link = host_link_bandwidth()
    ladder = (("dots", peak - 0.5 * act, False, 0.25 * fwd_s),
              ("full", peak - 0.9 * act, False, fwd_s),
              ("full", peak - 0.9 * act - opt, True,
               fwd_s + (2.0 * opt / link if link else 0.0)))
    for name, p2, off, over in ladder:
        if p2 <= hbm_limit:
            return name, off, float(over)
    # even the deepest rung stays over budget: report it anyway so the
    # row shows how close the best effort gets
    name, _, off, over = ladder[-1]
    return name, off, float(over)


# ---------------------------------------------------------------------------
# plan() — the one entry point — and the monitor ledger hook

_last_decision = None


def last_decision():
    """The most recent plan()/advise() decision (the /snapshot block)."""
    return _last_decision


def _record(p, table, auto):
    global _last_decision
    from .. import monitor as _monitor
    _monitor.counter("planner.plan").inc()
    if auto:
        _monitor.counter("planner.auto_pick").inc()
    n_cand = len(table) if table else 0
    _monitor.gauge("planner.candidates").set(n_cand)
    winner = table[0] if table else None
    if winner is not None:
        _monitor.gauge("planner.predicted_step_s").set(
            winner["pred_step_s"])
    hotspot = None
    try:
        from ..monitor import profile as _prof
        hs = _prof.last_summary(top_k=1)
        if hs and hs.get("hotspots"):
            hotspot = hs["hotspots"][0].get("region")
    except Exception:
        hotspot = None
    decision = {
        "ts": time.time(),
        "plan": p.plan_key(),
        "name": p.name,
        "mesh": p.sizes,
        "auto": bool(auto),
        "n_rules": len(p.rules),
        "candidates": n_cand,
        "chosen": dict(winner["sizes"]) if winner else dict(p.sizes),
        "predicted_step_s": (winner["pred_step_s"] if winner else None),
        "bound": winner["bound"] if winner else None,
        "peak_hbm_bytes": (winner.get("peak_hbm_bytes")
                           if winner else None),
        "hbm_limit_bytes": (winner.get("hbm_limit_bytes")
                            if winner else None),
        "infeasible": sum(1 for r in (table or [])
                          if not r.get("feasible", True)),
        "degraded": dict(p.degraded),
        # cross-link: the hotspot the profiler currently blames most —
        # grep the JSONL for this region to see what the layout choice
        # was reacting to
        "hotspot": hotspot,
        "table": [{k: r.get(k) for k in
                   ("rank", "sizes", "pred_step_s", "bound",
                    "degraded_frac", "peak_hbm_bytes", "feasible")}
                  for r in (table or [])[:8]],
    }
    _last_decision = decision
    if _monitor.enabled():
        _monitor.emit(kind="planner", **{
            k: v for k, v in decision.items() if k not in ("ts",)})
    return decision


def plan(rules=None, mesh=None, auto=False, cfg=None, n_devices=None,
         axes=("dp", "tp"), default=P(), data_axes=("dp",), name=None,
         record=True, **advise_kw):
    """THE entry point: build a MeshPlan, optionally letting the
    advisor pick the mesh.

    Manual: ``plan(rules, mesh=...)`` binds a rule set to a mesh.
    Auto:   ``plan(auto=True, cfg=megatron_cfg)`` (or with a profile
    report) ranks every factorization of the device count over `axes`,
    builds the winner's mesh, binds `rules` (MEGATRON_RULES when a cfg
    is given, TRANSFORMER_RULES otherwise) and records the decision in
    the monitor ledger. The returned plan carries the ranked table as
    ``.advice``."""
    if auto:
        table = advise(n_devices=n_devices, cfg=cfg, axes=axes,
                       **advise_kw)
        if not table:
            raise ValueError("advisor produced no candidate layouts")
        winner_row = next((r for r in table if r.get("feasible", True)),
                          None)
        if winner_row is None:
            lim = table[0].get("hbm_limit_bytes")
            raise ValueError(
                "advisor: every candidate layout exceeds the device "
                f"HBM budget ({lim and int(lim)} bytes) — shrink the "
                "model/batch, add devices, or raise "
                "PADDLE_TPU_HBM_LIMIT_BYTES")
        winner = winner_row["sizes"]
        if mesh is None:
            if cfg is not None:
                from .megatron import make_mesh as _mk
                mesh, _ = _mk(n_devices or len(jax.devices()),
                              sizes=winner)
            else:
                # keep size-1 axes on the mesh: rules that name them
                # stay valid (and harmless) instead of erroring
                mesh = _coll.make_mesh(
                    {a: int(s) for a, s in winner.items()})
        if rules is None:
            rules = MEGATRON_RULES if cfg is not None else \
                TRANSFORMER_RULES
        p = MeshPlan(rules, mesh=mesh, default=default,
                     data_axes=data_axes, name=name or "auto")
        p.advice = table
        if record:
            _record(p, table, auto=True)
        return p
    if rules is None:
        raise ValueError("plan() needs rules (or auto=True)")
    p = MeshPlan(rules, mesh=mesh, default=default, data_axes=data_axes,
                 name=name or "manual")
    p.advice = None
    if record:
        _record(p, None, auto=False)
    return p
