"""paddle_tpu.monitor.profile — per-operator cost attribution + roofline.

``monitor.xla`` records what a compiled executable costs as a whole
(flops, bytes, peak memory). This module answers the question ROADMAP
open item 3 actually asks — *which op, in which layer, is worth a
hand-written kernel?* — by parsing the optimized HLO text of a captured
executable, crediting every instruction's flops/bytes to the framework
scope that produced it, and ranking the resulting regions against the
device roofline.

Attribution rides on ``jax.named_scope``: XLA preserves the scope stack
of every traced eqn in instruction ``metadata={op_name=...}`` — through
fusion (inner instructions keep their own op_name), through the
backward pass (scopes resurface inside ``transpose(...)``/``jvp(...)``
wrappers), and through ``while``/``cond`` bodies. While ``jit.to_static``
traces a step (always, on the tracing thread only: :func:`tracing_step`),
or everywhere once profiling is enabled (``profile.enable()`` or
``PADDLE_TPU_PROFILE=1`` next to the monitor), every ``nn.Layer`` call,
optimizer update body, and the fused functional ops (softmax/xent/norm)
run under a stable registered scope name (``Linear_0``, ``opt.Adam``,
``F.softmax``, ...), so the ledger rows name real model parts, not HLO
serial numbers. The tape's backward runs under the phase scope ``bwd``;
:func:`phase_and_region` reads phase (``fwd``/``bwd``/``opt``/``none``)
and region off an ``op_name``, and :func:`instruction_ledger` says what
every instruction that runs holds of each — the side a device trace is
joined with (``benchmark/program_trace.py``).

The flop/byte model mirrors XLA's ``HloCostAnalysis`` conventions
(dot = 2·out·K, elementwise = 1/elem, reduce = in−out with the
``to_apply`` region folded in, transcendentals counted separately,
shape ops free), verified against ``Compiled.cost_analysis()`` — the
reconciliation is asserted to 1% in tests/test_profile.py.

Cost discipline: when disabled (the default) the labeling sites check
one module flag (``profile.live``) and nothing else happens — no scope
objects, no HLO parse. Scopes are HLO metadata: in a compiled step they
cost Python time once, while it is traced, and nothing per step.
``report()`` and ``instruction_ledger()`` are always explicit.

Usage::

    from paddle_tpu import monitor
    monitor.enable(); monitor.profile.enable()
    ... one jitted train step (aot-captured by monitor.xla) ...
    rep = monitor.profile.report()        # structured dict
    print(monitor.profile.format_table(rep))
"""
from __future__ import annotations

import contextlib
import os
import re
import threading
import time

import jax

__all__ = [
    "enable", "disable", "enabled", "scopes_on", "live", "armed",
    "tracing_step", "register_scope",
    "scopes", "layer_scope", "optimizer_scope", "fscope", "scope",
    "current_path", "reenter", "reset",
    "roofline_ceilings", "parse_hlo", "attribute", "report",
    "format_table", "last_report", "last_summary",
    "phase_and_region", "instruction_ledger", "PHASES", "BWD_SCOPE",
]

UNATTRIBUTED = "<unattributed>"

# scope kinds: "root" scopes (the to_static function name) exist
# so whole-step labels are recognized WITHOUT counting as attribution —
# everything lives under the root, so crediting it would make the ≥90%
# attribution bar trivially true.
_ATTRIBUTING_KINDS = ("layer", "optimizer", "functional", "op")

# kinds that exist to be recognized and never count as attribution:
# "root" (see above) and "phase" (the tape's backward, BWD_SCOPE)
PHASES = ("fwd", "bwd", "opt", "none")
BWD_SCOPE = "bwd"

_lock = threading.Lock()
scopes_on = False           # profile.enable(): label eager code too
live = False                # what nn.Layer/__call__, ops, optimizer read:
#                             scopes_on, or some thread is tracing a step
_tracing = 0                # threads inside tracing_step()
_scopes = {}                # scope name -> kind
_scope_phase = {}           # scope name -> phase, for the few that set one
_layer_counters = {}        # class name -> next per-instance index
_last = None                # cached last report() result


# ---------------------------------------------------------------------------
# lifecycle + scope registry

def enable():
    """Arm scope labeling (one module-flag check at each site when off)."""
    global scopes_on, live
    with _lock:
        scopes_on = live = True


def disable():
    global scopes_on, live
    with _lock:
        scopes_on = False
        live = _tracing > 0


def enabled():
    return scopes_on


def armed():
    """Whether the calling thread labels: after ``enable()``, or while it
    traces a compiled step. The sites ask this only once ``live`` is set,
    so a trace on one thread costs eager code on another this one call
    per site, and labels nothing there."""
    return scopes_on or _tls.__dict__.get("tracing", 0) > 0


@contextlib.contextmanager
def tracing_step():
    """Arm the labelling sites on the calling thread for the duration of
    one trace of a compiled step (``jit.StaticFunction``), and put things
    back after: ``scopes_on`` is never touched."""
    global live, _tracing
    depth = _tls.__dict__.get("tracing", 0)
    with _lock:
        _tracing += 1
        live = True
    _tls.tracing = depth + 1
    try:
        yield
    finally:
        _tls.tracing = depth
        with _lock:
            _tracing -= 1
            live = scopes_on or _tracing > 0


def register_scope(name, kind="layer", phase=None):
    """Register ``name`` as a scope the ledger recognizes (kind: layer /
    optimizer / functional / op, which attribute; root / phase, which do
    not). ``phase`` puts everything under it into that phase."""
    with _lock:
        _scopes[str(name)] = kind
        if phase is not None:
            _scope_phase[str(name)] = phase
    return name


def scopes():
    with _lock:
        return dict(_scopes)


def layer_scope(layer):
    """Stable per-instance scope name for an nn.Layer: ``<Cls>_<k>`` in
    first-call order (deterministic for a fixed model + call order)."""
    name = layer.__dict__.get("_profile_scope")
    if name is None:
        cls = type(layer).__name__
        with _lock:
            k = _layer_counters.get(cls, 0)
            _layer_counters[cls] = k + 1
            name = f"{cls}_{k}"
            _scopes[name] = "layer"
        layer.__dict__["_profile_scope"] = name
    elif name not in _scopes:
        # a profile.reset() between runs cleared the registry but the
        # instance keeps its stable name — re-register, don't re-number
        with _lock:
            _scopes[name] = "layer"
    return name


def optimizer_scope(opt):
    """``opt.<Cls>`` — one scope per optimizer class instance."""
    name = getattr(opt, "_profile_scope", None)
    if name is None:
        name = f"opt.{type(opt).__name__}"
        try:
            opt._profile_scope = name
        except Exception:
            pass
    if name not in _scopes:
        register_scope(name, "optimizer", phase="opt")
    return name


def fscope(name):
    """Register-and-return a functional-op scope (``F.softmax`` ...)."""
    if name not in _scopes:
        with _lock:
            _scopes[name] = "functional"
    return name


# The scope path of the calling thread, kept beside jax's own name stack
# because that one cannot be read back: jax names the ops of a vjp by
# the stack at the time the vjp is CALLED, not where it was built, so
# the tape (dispatch.apply -> autograd.backward) records the forward
# op's path here and re-enters it around the op's backward. Without
# that, every backward op of an eager-tape step is "<unattributed>".
_tls = threading.local()


@contextlib.contextmanager
def scope(name):
    """``jax.named_scope(name)``, with ``name`` also pushed on this
    thread's scope path (see :func:`current_path`)."""
    path = _tls.__dict__.setdefault("path", [])
    path.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        path.pop()


def current_path():
    """The scopes entered through :func:`scope` on this thread, outermost
    first — what a tape node keeps to label its backward."""
    return tuple(_tls.__dict__.get("path", ()))


@contextlib.contextmanager
def reenter(path):
    """Re-enter a recorded scope path (around a tape node's vjp call)."""
    with contextlib.ExitStack() as stack:
        for name in path:
            stack.enter_context(jax.named_scope(name))
        yield


def backward_scope():
    """The phase scope the tape's backward sweep runs under
    (``autograd.backward``). Not pushed on the scope path: a node taped
    during the sweep keeps its layer's path and no ``bwd`` in it."""
    register_scope(BWD_SCOPE, "phase", phase="bwd")
    return jax.named_scope(BWD_SCOPE)


def reset():
    """Clear registered scopes, per-class counters and the cached
    report (labeling flag is left as-is)."""
    global _last
    with _lock:
        _scopes.clear()
        _scope_phase.clear()
        _layer_counters.clear()
    _last = None


# ---------------------------------------------------------------------------
# roofline ceilings

# CPU-side HLO ranking only (the test mesh has no roofline of its own):
# rank fusion candidates against an *assumed* v5e and say so in the
# report. On a tpu backend there is no assumption — a device kind the
# peaks table (monitor/step.py) does not know is an error.
ASSUMED_KIND = "TPU v5e"


def roofline_ceilings(device_kind=None):
    """Flops + HBM-bandwidth ceilings for ``device_kind`` (default:
    $PADDLE_TPU_ROOFLINE_DEVICE, then the local device). Off-TPU an
    unknown kind ranks against an *assumed*, labelled v5e; on a tpu
    backend it raises. $PADDLE_TPU_FLOPS_CEILING (flops/s) and
    $PADDLE_TPU_HBM_GBPS override the tables."""
    from . import step as _step
    from ..device import is_tpu_backend
    kind = device_kind or os.environ.get("PADDLE_TPU_ROOFLINE_DEVICE")
    if not kind:
        kind = jax.local_devices()[0].device_kind
    kind = str(kind)
    flops, bw = _step.ceilings_for_kind(kind)
    assumed = False
    if (flops is None or bw is None) and is_tpu_backend():
        raise ValueError(
            f"device kind {kind!r} is not in the peaks table "
            f"(paddle_tpu/monitor/step.py); add it with its source "
            f"instead of assuming another chip's roofline")
    if flops is None or bw is None:
        a_flops, a_bw = _step.ceilings_for_kind(ASSUMED_KIND)
        if flops is None:
            flops, assumed = a_flops, True
        if bw is None:
            bw, assumed = a_bw, True
        kind = f"{kind or 'unknown'} (assumed {ASSUMED_KIND})"
    env_f = os.environ.get("PADDLE_TPU_FLOPS_CEILING")
    if env_f:
        flops = float(env_f)
    env_b = os.environ.get("PADDLE_TPU_HBM_GBPS")
    if env_b:
        bw = float(env_b) * 1e9
    if env_f and env_b:
        assumed = False      # both ceilings pinned by the operator
    return {
        "device_kind": kind,
        "peak_flops": float(flops),
        "hbm_bytes_per_sec": float(bw),
        "ridge_flops_per_byte": float(flops) / float(bw),
        "assumed": assumed,
    }


# ---------------------------------------------------------------------------
# HLO text parsing (XLA HloCostAnalysis-compatible accounting)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_TYPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|f8\w+|s4|s8|s16|s32|s64|u4|u8|u16|u32|u64"
    r"|c64|c128)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_COMP_RE = re.compile(r"^\s*(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_OPNAME_RE = re.compile(r'op_name="([^"]*)"')
_REF_RE = {
    "to_apply": re.compile(r"to_apply=%?([\w.\-]+)"),
    "calls": re.compile(r"calls=%?([\w.\-]+)"),
    "inline": re.compile(r"(?:condition|body)=%?([\w.\-]+)"),
    "inline_set": re.compile(
        r"(?:branch_computations|called_computations)=\{([^}]*)\}"),
}
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_WINDOW_RE = re.compile(r"window=\{[^}]*\bsize=([0-9x]+)")
_DIMLABELS_RE = re.compile(r"dim_labels=(\w+)_(\w+)->(\w+)")
_WRAPPERS = (r"(jit|jvp|vjp|transpose|vmap|pmap|xmap|remat|checkpoint|"
             r"custom_jvp|custom_vjp|shard_map)")
_WRAPPER_RE = re.compile(r"^" + _WRAPPERS + r"\((.*)\)$")
_WRAPPER_OPEN_RE = re.compile(r"^" + _WRAPPERS + r"\(")

# 1 flop per output element (HloCostAnalysis default for elementwise)
_ELEMENTWISE = frozenset((
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "compare", "select", "and", "or", "xor", "not", "negate", "abs",
    "sign", "floor", "ceil", "round-nearest-even", "round-nearest-afz",
    "power", "remainder", "clamp", "shift-left",
    "shift-right-arithmetic", "shift-right-logical", "is-finite",
    "popcnt", "count-leading-zeros", "stochastic-convert",
))
# counted in the separate `transcendentals` bucket, 0 flops
_TRANSCENDENTAL = frozenset((
    "exponential", "exponential-minus-one", "log", "log-plus-one",
    "logistic", "rsqrt", "sqrt", "cbrt", "tanh", "sine", "cosine",
    "tan", "atan2", "erf", "erf-inv", "expm1",
))
# pure bookkeeping: never a ledger row of its own
_SKIP_OPS = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "partition-id", "replica-id",
))


def _shape_elems(dims):
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


def _type_bytes(s):
    """Total bytes of every array shape mentioned in a type string
    (a tuple type sums its components)."""
    total = 0
    for dt, dims in _TYPE_RE.findall(s):
        total += _shape_elems(dims) * _DTYPE_BYTES.get(dt, 4)
    return total


def _type_elems(s):
    """Total element count across array shapes in a type string."""
    total = 0
    for _dt, dims in _TYPE_RE.findall(s):
        total += _shape_elems(dims)
    return total


def _first_shape(s):
    m = _TYPE_RE.search(s)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


def _balanced(s, i, open_ch="(", close_ch=")"):
    """Index one past the matching close bracket for s[i] == open_ch."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] == open_ch:
            depth += 1
        elif s[j] == close_ch:
            depth -= 1
            if depth == 0:
                return j + 1
    return len(s)


def _split_top(s):
    """Split an operand list at top-level commas."""
    parts, depth, start = [], 0, 0
    for j, ch in enumerate(s):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            parts.append(s[start:j].strip())
            start = j + 1
    tail = s[start:].strip()
    if tail:
        parts.append(tail)
    return parts


def _parse_instr(line):
    """One HLO instruction line -> dict, or None for non-instructions."""
    m = _INSTR_RE.match(line)
    if m is None:
        return None
    root, name, rest = bool(m.group(1)), m.group(2), m.group(3)
    # output type: tuple '(...)' or a single token up to the next space
    if rest.startswith("("):
        end = _balanced(rest, 0)
        out_type = rest[:end]
    else:
        end = rest.find(" ")
        if end < 0:
            return None
        out_type = rest[:end]
    rest = rest[end:].lstrip()
    om = re.match(r"([a-z][\w\-]*)\(", rest)
    if om is None:
        return None
    opcode = om.group(1)
    op_end = _balanced(rest, om.end() - 1)
    operands = rest[om.end():op_end - 1]
    attrs = rest[op_end:]
    nm = _OPNAME_RE.search(attrs)
    return {
        "name": name, "opcode": opcode, "out_type": out_type,
        "operands": _split_top(operands), "attrs": attrs,
        "op_name": nm.group(1) if nm else "", "root": root,
    }


_BARE_OPERAND_RE = re.compile(r"^%?([\w.\-]+)$")


def _type_bare_operands(instrs):
    """The XLA of jaxlib 0.9 prints an operand as a bare ``%name``; older
    text carried its type (``f32[8,16]{1,0} %name``), which the flop and
    byte models read. Put the defining instruction's type back in front
    of every bare operand, so one form reaches the rest of the parser."""
    types = {i["name"]: i["out_type"] for i in instrs}
    for instr in instrs:
        if instr["opcode"] == "parameter":
            continue        # its "operand" is the parameter number
        ops = instr["operands"]
        for k, op in enumerate(ops):
            m = _BARE_OPERAND_RE.match(op.strip())
            if m and m.group(1) in types:
                ops[k] = f"{types[m.group(1)]} %{m.group(1)}"


def parse_hlo(text):
    """Parse optimized HLO text into ``{computation_name: {"entry": bool,
    "instrs": [...]}}`` plus reference sets. Returns (comps, entry_name,
    refs) where refs maps kind -> set of computation names referenced as
    to_apply (folded), calls (fused) or control-flow bodies (inline)."""
    comps, entry = {}, None
    cur = None
    for line in text.splitlines():
        stripped = line.strip()
        if not stripped:
            continue
        if stripped == "}":
            cur = None
            continue
        if stripped.endswith("{") and ("->" in stripped
                                       or stripped.startswith("ENTRY")):
            cm = _COMP_RE.match(stripped)
            if cm:
                cur = cm.group(2)
                comps[cur] = {"entry": bool(cm.group(1)), "instrs": []}
                if cm.group(1):
                    entry = cur
                continue
        if cur is None:
            continue
        instr = _parse_instr(line)
        if instr is not None:
            comps[cur]["instrs"].append(instr)
    for comp in comps.values():
        _type_bare_operands(comp["instrs"])
    refs = {"to_apply": set(), "calls": set(), "inline": set()}
    for comp in comps.values():
        for instr in comp["instrs"]:
            attrs = instr["attrs"]
            # a reduce folds its to_apply in; a `call` runs it
            applied = "inline" if instr["opcode"] == "call" else "to_apply"
            for n in _REF_RE["to_apply"].findall(attrs):
                refs[applied].add(n)
            for n in _REF_RE["calls"].findall(attrs):
                refs["calls"].add(n)
            for n in _REF_RE["inline"].findall(attrs):
                refs["inline"].add(n)
            for group in _REF_RE["inline_set"].findall(attrs):
                for tok in group.split(","):
                    tok = tok.strip().lstrip("%")
                    if tok:
                        refs["inline"].add(tok)
    return comps, entry, refs


def _window_field(window, key, n, default):
    """One ``key=AxBx..`` field of a ``window={...}`` attribute as n
    strings, or n defaults."""
    m = re.search(r"\b" + key + r"=([\w\-]+)", window)
    return m.group(1).split("x") if m else [default] * n


def _conv_flops(instr, out_elems):
    """2 x multiply-adds of a convolution, HloCostAnalysis's way: per
    spatial dimension the (output position, kernel position) pairs that
    land on a real input element — not in the padding, not in a hole of
    a dilated input — times batch, output features and input features
    per group. The count matters on TPU, where a batched matmul is a
    convolution whose batch dimensions sit in a dilated window with one
    live position per output (``size=64x12 stride=63x11
    lhs_dilate=64x12``): kernel size times output size would count it
    64 x 12 times over."""
    dm = _DIMLABELS_RE.search(instr["attrs"])
    if dm is None or len(instr["operands"]) < 2:
        return 2 * out_elems
    lhs_spec, rhs_spec, out_spec = dm.groups()
    lhs = _first_shape(instr["operands"][0])
    rhs = _first_shape(instr["operands"][1])
    out = _first_shape(instr["out_type"])
    if (len(lhs), len(rhs), len(out)) != (len(lhs_spec), len(rhs_spec),
                                          len(out_spec)):
        return 2 * out_elems
    n = len(out_spec) - 2
    wm = re.search(r"window=\{([^}]*)\}", instr["attrs"])
    window = wm.group(1) if wm else ""
    stride = [int(x) for x in _window_field(window, "stride", n, "1")]
    pad_low = [int(x.split("_")[0])
               for x in _window_field(window, "pad", n, "0_0")]
    lhs_dil = [int(x) for x in _window_field(window, "lhs_dilate", n, "1")]
    rhs_dil = [int(x) for x in _window_field(window, "rhs_dilate", n, "1")]
    pairs = 1
    for d in range(n):
        c = str(d)
        size_in = lhs[lhs_spec.index(c)]
        size_k = rhs[rhs_spec.index(c)]
        size_out = out[out_spec.index(c)]
        live = 0
        for k in range(size_k):
            for o in range(size_out):
                at = o * stride[d] - pad_low[d] + k * rhs_dil[d]
                if at >= 0 and at % lhs_dil[d] == 0 \
                        and at // lhs_dil[d] < size_in:
                    live += 1
        pairs *= live
    # the result's batch is per batch group and the kernel's input
    # features are per feature group already
    return 2 * out[out_spec.index("b")] * out[out_spec.index("f")] \
        * rhs[rhs_spec.index("i")] * pairs


def _instr_flops(instr, comps):
    """(flops, transcendentals) for one instruction, mirroring
    HloCostAnalysis conventions. Fusions sum their called computation."""
    opcode = instr["opcode"]
    if opcode == "fusion":
        f = t = 0
        for target in _REF_RE["calls"].findall(instr["attrs"]):
            comp = comps.get(target)
            if comp is None:
                continue
            for inner in comp["instrs"]:
                fi, ti = _instr_flops(inner, comps)
                f += fi
                t += ti
        return f, t
    out_elems = _type_elems(instr["out_type"])
    if opcode == "dot":
        contracted = 1
        cm = _CONTRACT_RE.search(instr["attrs"])
        if cm and instr["operands"]:
            lhs_dims = _first_shape(instr["operands"][0])
            for idx in cm.group(1).split(","):
                if idx and int(idx) < len(lhs_dims):
                    contracted *= lhs_dims[int(idx)]
        return 2 * out_elems * contracted, 0
    if opcode == "convolution":
        return _conv_flops(instr, out_elems), 0
    if opcode == "reduce":
        ops = instr["operands"]
        arrays = ops[:max(1, len(ops) // 2)]
        in_elems = sum(_type_elems(o) for o in arrays)
        return max(0, in_elems - out_elems), 0
    if opcode == "reduce-window":
        wm = _WINDOW_RE.search(instr["attrs"])
        window = 1
        if wm:
            for d in wm.group(1).split("x"):
                if d:
                    window *= int(d)
        return out_elems * max(0, window - 1), 0
    if opcode in _TRANSCENDENTAL:
        return 0, out_elems
    if opcode in _ELEMENTWISE:
        return out_elems, 0
    return 0, 0


def _instr_bytes(instr):
    """Operand + output bytes (the HloCostAnalysis bytes_accessed
    convention: every operand read once, the output written once)."""
    b = _type_bytes(instr["out_type"])
    for op in instr["operands"]:
        b += _type_bytes(op)
    return b


def _scope_tokens(op_name):
    """named_scope path segments of an op_name, with jit()/jvp()/
    transpose()/... wrappers peeled recursively — backward-pass ops
    carry their forward scope inside transpose(jvp(scope)). A wrapper
    may span segments (``transpose(jvp(A/B/kernel))``: a custom_vjp
    keeps the path it was defined under): its opening is peeled off the
    first segment and its closing off the last."""
    toks = []
    for raw in op_name.split("/"):
        t = raw.strip()
        while True:
            m = _WRAPPER_RE.match(t)
            if m is not None:
                t = m.group(2)
                continue
            m = _WRAPPER_OPEN_RE.match(t)
            if m is None or t.count("(") <= t.count(")"):
                break
            t = t[m.end():]
        while t.endswith(")") and t.count(")") > t.count("("):
            t = t[:-1]
        if t:
            toks.append(t)
    return toks


def _region_of(op_name, scope_map, tokens=None):
    """(region_path, leaf_scope) from an op_name given the registry —
    the joined chain of registered attributable scopes, or
    (UNATTRIBUTED, None) when no registered scope appears."""
    hits = []
    for t in _scope_tokens(op_name) if tokens is None else tokens:
        # once each: a path re-entered for a backward op (or kept inside
        # a custom_vjp's wrapper) names the same scopes again
        if scope_map.get(t) in _ATTRIBUTING_KINDS and t not in hits:
            hits.append(t)
    if not hits:
        return UNATTRIBUTED, None
    return "/".join(hits), hits[-1]


def phase_and_region(op_name, scope_map=None, phase_map=None):
    """(phase, region) of an op_name. The region is :func:`_region_of`'s.
    The phase is ``opt`` under a scope registered with that phase
    (``opt.<Cls>``, ``arena.pack``), else ``bwd`` under the tape's
    backward scope, else ``fwd`` under a root scope (a compiled step's),
    else ``none``: an instruction the compiler made, or eager code."""
    scope_map = _scopes if scope_map is None else scope_map
    phase_map = _scope_phase if phase_map is None else phase_map
    tokens = _scope_tokens(op_name)
    phase = "none"
    for t in tokens:
        marked = phase_map.get(t)
        if marked == "opt":
            phase = "opt"
            break
        if marked == "bwd":
            phase = "bwd"
        elif phase == "none" and scope_map.get(t) == "root":
            phase = "fwd"
    return phase, _region_of(op_name, scope_map, tokens)[0]


def _running_computations(comps, entry, refs):
    """Names of the computations whose instructions run as instructions
    of their own: ENTRY and control-flow bodies, not the folded
    (to_apply) and fused (calls) ones."""
    inline = refs["inline"] - refs["calls"] - refs["to_apply"]
    return [n for n in [entry] + sorted(inline - {entry}) if n in comps]


def attribute(text, scope_map=None):
    """Parse HLO ``text`` and attribute per-instruction cost to
    registered scopes. Returns a dict with ``ops`` rows (one per
    top-level instruction that does work), ``total_flops``,
    ``attributed_flops``, ``attributed_frac``, ``transcendentals``.

    Attribution is finest-granularity: a fusion's flops are credited
    per *inner* instruction op_name, so one fusion spanning two layers
    splits correctly; the row's own ``region`` is the dominant-flop
    region (falling back to the fusion's op_name when inner flops are
    all zero)."""
    scope_map = dict(_scopes) if scope_map is None else dict(scope_map)
    comps, entry, refs = parse_hlo(text)
    if entry is None:
        return {"ops": [], "total_flops": 0.0, "attributed_flops": 0.0,
                "attributed_frac": 0.0, "transcendentals": 0.0}

    ops = []
    total_f = attr_f = total_t = 0.0
    for cname in _running_computations(comps, entry, refs):
        for instr in comps[cname]["instrs"]:
            if instr["opcode"] in _SKIP_OPS:
                continue
            flops, trans = _instr_flops(instr, comps)
            nbytes = _instr_bytes(instr)
            if instr["opcode"] == "fusion":
                # split the fusion's flops across inner-instruction
                # scopes; dominant region becomes the row's region
                by_region = {}
                a = 0.0
                for target in _REF_RE["calls"].findall(instr["attrs"]):
                    comp = comps.get(target)
                    if comp is None:
                        continue
                    for inner in comp["instrs"]:
                        fi, _ti = _instr_flops(inner, comps)
                        reg, _leaf = _region_of(inner["op_name"],
                                                scope_map)
                        by_region[reg] = by_region.get(reg, 0.0) + fi
                        if reg != UNATTRIBUTED:
                            a += fi
                if by_region and any(v > 0 for v in by_region.values()):
                    region = max(by_region, key=by_region.get)
                else:
                    region, _ = _region_of(instr["op_name"], scope_map)
                    if region != UNATTRIBUTED:
                        a = flops
                leaf = region.rsplit("/", 1)[-1] \
                    if region != UNATTRIBUTED else None
                attributed = a
            else:
                region, leaf = _region_of(instr["op_name"], scope_map)
                attributed = flops if region != UNATTRIBUTED else 0.0
            if flops == 0 and trans == 0 and nbytes == 0:
                continue
            total_f += flops
            total_t += trans
            attr_f += attributed
            ops.append({
                "name": instr["name"], "opcode": instr["opcode"],
                "region": region, "scope": leaf,
                "scope_kind": scope_map.get(leaf),
                "flops": float(flops), "bytes": float(nbytes),
                "transcendentals": float(trans),
                "attributed_flops": float(attributed),
            })
    return {
        "ops": ops,
        "total_flops": float(total_f),
        "attributed_flops": float(attr_f),
        "attributed_frac": (attr_f / total_f) if total_f else 0.0,
        "transcendentals": float(total_t),
    }


# ---------------------------------------------------------------------------
# what each instruction of a captured executable holds, by phase and region

_MODULE_RE = re.compile(r"^HloModule\s+([^\s,]+)", re.M)
_OPERAND_NAME_RE = re.compile(r"%?([\w.\-]+)$")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
# they read or write a window of their big operand, not all of it
_WINDOWED = frozenset(("slice", "dynamic-slice", "gather"))
# they change a value's type or layout and compute nothing on it
_FORMATTING = frozenset(("bitcast", "get-tuple-element", "convert", "copy",
                         "transpose", "reshape", "broadcast"))


def _kernel_name(instr):
    """The ``name=`` of the ``pl.pallas_call`` an instruction is, or None:
    the op_name segment in front of ``pallas_call``, wrappers peeled."""
    if instr["opcode"] != "custom-call" or MOSAIC_TARGET not in instr["attrs"]:
        return None
    tokens = _scope_tokens(instr["op_name"])
    names = [prev for prev, tok in zip(tokens, tokens[1:])
             if tok == "pallas_call"]
    return names[-1] if names else None


def _operand_name(operand):
    m = _OPERAND_NAME_RE.search(operand.strip())
    return m.group(1) if m else None


def _by_name_and_users(instrs):
    """({name: instruction}, {name: the instructions that read it, in
    schedule order}) of one computation."""
    by_name = {i["name"]: i for i in instrs}
    users = {}
    for instr in instrs:
        for operand in instr["operands"]:
            users.setdefault(_operand_name(operand), []).append(instr)
    return by_name, users


def _boundary_bytes(instrs):
    """Bytes each instruction of a FUSED computation moves across the
    fusion's boundary, by name: a fusion parameter's bytes go to the
    first instruction that computes on it, the result's to the
    instruction that computes it; conversions and changes of layout on
    the way pass them through (where nothing else is there, the first of
    them takes them). What passes between the inner instructions never
    reaches memory and is not counted, so the parts add up to the
    fusion's own operands and result."""
    by_name, users = _by_name_and_users(instrs)
    moved = {i["name"]: 0 for i in instrs if i["opcode"] not in _SKIP_OPS}

    def reader(param):
        first, front, seen = None, [param], set()
        while front:
            nxt = []
            for instr in front:
                for user in users.get(instr["name"], ()):
                    if user["name"] in seen:
                        continue
                    seen.add(user["name"])
                    if user["opcode"] not in _FORMATTING:
                        return user
                    first = first or (user if user["name"] in moved
                                      else None)
                    nxt.append(user)
            front = nxt
        return first

    def writer(name):
        first, instr = None, by_name.get(name)
        while instr is not None and instr["opcode"] in _FORMATTING \
                and instr["operands"]:
            first = first or (instr if instr["name"] in moved else None)
            instr = by_name.get(_operand_name(instr["operands"][0]))
        if instr is not None and instr["name"] in moved:
            return instr
        return first

    for param in instrs:
        if param["opcode"] != "parameter":
            continue
        instr = reader(param)
        if instr is not None:
            moved[instr["name"]] += _type_bytes(
                instr["out_type"] if instr["opcode"] in _WINDOWED
                else param["out_type"])
    root = next((i for i in instrs if i["root"]), None)
    results = []
    if root is not None:
        results = [_operand_name(o) for o in root["operands"]] \
            if root["opcode"] == "tuple" else [root["name"]]
    for name in results:
        instr = writer(name)
        if instr is None:
            continue
        written = by_name[name]["out_type"]
        if instr["opcode"] == "dynamic-update-slice" \
                and len(instr["operands"]) > 1:
            written = instr["operands"][1]      # the update, in place
        moved[instr["name"]] += _type_bytes(written)
    return moved


def _labelled(op_name):
    """Whether an op_name holds a scope path. The compiler's own
    instructions have none at all, and a copy of an argument is named
    after the argument alone (``state_vals[397]``)."""
    return "/" in op_name


def _serves(instrs):
    """{name: (op_name, name of the instruction it was taken from)} for
    the instructions of one computation that carry no scope path: what
    the compiler made to move data (``copy-start``/``copy-done`` and
    ``slice-start``/``slice-done`` prefetches, ``ConcatBitcast``, layout
    copies of arguments) belongs to the labelled instruction it serves —
    the first one downstream in the schedule, else the nearest one
    upstream."""
    by_name, users = _by_name_and_users(instrs)

    def nearest(start, step):
        seen, front = {start["name"]}, [start]
        for _ in range(8):
            nxt = []
            for instr in front:
                for other in step(instr):
                    if other is None or other["name"] in seen:
                        continue
                    if _labelled(other["op_name"]):
                        return other
                    seen.add(other["name"])
                    nxt.append(other)
            front = nxt
        return None

    out = {}
    for instr in instrs:
        if _labelled(instr["op_name"]) or instr["opcode"] in _SKIP_OPS:
            continue
        found = nearest(instr, lambda i: users.get(i["name"], ())) or \
            nearest(instr, lambda i: [by_name.get(_operand_name(o))
                                      for o in i["operands"]])
        if found is not None:
            out[instr["name"]] = (found["op_name"], found["name"])
    return out


def _callers(comps):
    """{computation: the ``while`` / ``conditional`` / ``call`` instruction
    that runs it as a body, a condition or a branch}."""
    out = {}
    for comp in comps.values():
        for instr in comp["instrs"]:
            attrs = instr["attrs"]
            ran = _REF_RE["inline"].findall(attrs)
            for group in _REF_RE["inline_set"].findall(attrs):
                ran += [t.strip().lstrip("%") for t in group.split(",")]
            if instr["opcode"] == "call":
                ran += _REF_RE["to_apply"].findall(attrs)
            for target in ran:
                if target:
                    out[target] = instr
    return out


def _loop_it_runs_in(cname, callers, comps):
    """(op_name, name) of the nearest labelled instruction that runs the
    computation ``cname``, or (None, None): what a loop body's own data
    movement (a prefetch that only the body's ROOT tuple reads) serves
    is the loop."""
    seen = set()
    while cname in callers and cname not in seen:
        seen.add(cname)
        caller = callers[cname]
        if _labelled(caller["op_name"]):
            return caller["op_name"], caller["name"]
        cname = next((c for c, comp in comps.items()
                      if caller in comp["instrs"]), None)
    return None, None


def _instruction_parts(instr, comps, scope_map, phase_map, op_name=None):
    """{(phase, region): [flops, bytes]} of one top-level instruction. A
    fusion is split over its inner instructions' own op_names; the inner
    instructions the compiler made (no op_name) follow the rest.
    ``op_name`` stands in where the instruction has none of its own."""
    parts = {}

    def add(op_name, flops, nbytes):
        key = phase_and_region(op_name, scope_map, phase_map)
        part = parts.setdefault(key, [0.0, 0.0])
        part[0] += flops
        part[1] += nbytes

    if instr["opcode"] == "fusion":
        for target in _REF_RE["calls"].findall(instr["attrs"]):
            inner_instrs = comps.get(target, {"instrs": ()})["instrs"]
            moved = _boundary_bytes(inner_instrs)
            for inner in inner_instrs:
                if inner["opcode"] in _SKIP_OPS \
                        or not _labelled(inner["op_name"]):
                    continue
                flops, trans = _instr_flops(inner, comps)
                add(inner["op_name"], flops + trans, moved[inner["name"]])
    if not parts:
        flops, trans = _instr_flops(instr, comps)
        add(op_name or instr["op_name"], flops + trans, _instr_bytes(instr))
    return parts


def instruction_ledger(label=None, hlo=None, scope_map=None,
                       phase_map=None):
    """What every instruction that runs holds, by phase and region: the
    side of the join that a device trace's own times are credited
    through. One row per top-level instruction of ENTRY and of every
    control-flow body, of the executable ``monitor.xla`` keeps under
    ``label`` (default: of every executable it keeps), or of ``hlo=``
    text::

        {"module": "jit_bert_step",   # as the trace's "XLA Modules" line
         "label": "jit.bert_step",    # monitor.xla's
         "name": "fusion.123",        # as the trace's "XLA Ops" line
         "opcode": "fusion",
         "kernel": None,              # a pl.pallas_call's name=
         "serves": None,              # unlabelled: whose labels it took
         "parts": [{"phase": "bwd", "region": "BertLayer_3/Linear_0",
                    "flops": ..., "bytes": ...}, ...]}

    ``flops`` and ``bytes`` are the cost MODEL's (:func:`attribute`'s
    conventions; a fusion's bytes are what crosses its boundary), there to
    split an instruction that holds several phases; what an instruction
    took is the trace's to say. Parses when called and never before:
    nothing of this may run in a step, in set-up or in a timed window."""
    from . import xla as _xla
    scope_map = dict(_scopes) if scope_map is None else dict(scope_map)
    phase_map = dict(_scope_phase) if phase_map is None else dict(phase_map)
    if hlo is not None:
        texts = [(label, hlo)]
    else:
        texts = []
        for lb in ([str(label)] if label is not None else _xla.labels()):
            exe = _xla.executable(lb)
            try:
                texts.append((lb, exe.as_text()))
            except Exception:
                continue
    rows = []
    for lb, text in texts:
        comps, entry, refs = parse_hlo(text)
        if entry is None:
            continue
        m = _MODULE_RE.search(text)
        module = m.group(1) if m else None
        callers = _callers(comps)
        for cname in _running_computations(comps, entry, refs):
            serves = _serves(comps[cname]["instrs"])
            loop = _loop_it_runs_in(cname, callers, comps)
            for instr in comps[cname]["instrs"]:
                if instr["opcode"] in _SKIP_OPS:
                    continue
                op_name, served = serves.get(
                    instr["name"],
                    (None, None) if _labelled(instr["op_name"]) else loop)
                parts = _instruction_parts(instr, comps, scope_map,
                                           phase_map, op_name)
                rows.append({
                    "module": module, "label": lb, "name": instr["name"],
                    "opcode": instr["opcode"],
                    "kernel": _kernel_name(instr), "serves": served,
                    "parts": [{"phase": ph, "region": reg,
                               "flops": float(f), "bytes": float(b)}
                              for (ph, reg), (f, b) in sorted(parts.items())],
                })
    return rows


# ---------------------------------------------------------------------------
# roofline classification + the ranked fusion menu

def _rooflined(ops, ceil):
    peak, bw = ceil["peak_flops"], ceil["hbm_bytes_per_sec"]
    for op in ops:
        t_c = op["flops"] / peak
        t_m = op["bytes"] / bw
        est = max(t_c, t_m)
        op["arith_intensity"] = (op["flops"] / op["bytes"]
                                 if op["bytes"] else None)
        op["est_time_s"] = est
        op["bound"] = "compute" if t_c >= t_m else "memory"
        op["mfu"] = (t_c / est) if est > 0 else None
        op["headroom_s"] = est - t_c
    return ops


def _regions(ops):
    regions = {}
    for op in ops:
        r = regions.setdefault(op["region"], {
            "region": op["region"], "scope_kind": op["scope_kind"],
            "ops": 0, "flops": 0.0, "bytes": 0.0,
            "transcendentals": 0.0, "est_time_s": 0.0,
            "compute_time_s": 0.0, "headroom_s": 0.0,
        })
        r["ops"] += 1
        r["flops"] += op["flops"]
        r["bytes"] += op["bytes"]
        r["transcendentals"] += op["transcendentals"]
        r["est_time_s"] += op["est_time_s"]
        r["compute_time_s"] += op["est_time_s"] - op["headroom_s"]
        r["headroom_s"] += op["headroom_s"]
    out = []
    for r in regions.values():
        r["bound"] = ("memory" if r["headroom_s"] > r["compute_time_s"]
                      else "compute")
        r["mfu"] = (r["compute_time_s"] / r["est_time_s"]
                    if r["est_time_s"] > 0 else None)
        out.append(r)
    # ranking: headroom first (time a perfect fusion could claw back),
    # flops and name as deterministic tie-breaks
    out.sort(key=lambda r: (-r["headroom_s"], -r["flops"], r["region"]))
    return out


def report(label=None, top_k=10, hlo=None, device_kind=None,
           emit_records=True):
    """Build the per-op cost ledger for a captured executable.

    ``label`` picks a ``monitor.xla`` capture (default: newest);
    ``hlo=`` profiles a raw HLO string instead. Returns a dict with
    per-op rows, per-region aggregation, ranked ``hotspots`` (top_k by
    fusion headroom), ceilings, and the reconciliation ratio against
    XLA's own ``cost_analysis()`` flop count — or None when nothing has
    been captured. Emits one JSONL ``hotspot`` record per hotspot and a
    ``profile.attributed_frac.<label>`` gauge when the monitor is on."""
    global _last
    from . import xla as _xla
    xla_flops = None
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
        xla_flops = _xla.flops(label)
    ceil = roofline_ceilings(device_kind)
    attr = attribute(hlo)
    ops = _rooflined(attr["ops"], ceil)
    ops.sort(key=lambda o: (-o["est_time_s"], o["name"]))
    regions = _regions(ops)
    hotspots = []
    for rank, r in enumerate(regions[:max(0, int(top_k))], start=1):
        hotspots.append(dict(r, rank=rank))
    recon = (attr["total_flops"] / xla_flops
             if xla_flops else None)
    rep = {
        "kind": "profile_report",
        "ts": time.time(),
        "label": label,
        "ceilings": ceil,
        "total_flops": attr["total_flops"],
        "attributed_flops": attr["attributed_flops"],
        "attributed_frac": attr["attributed_frac"],
        "transcendentals": attr["transcendentals"],
        "xla_flops": xla_flops,
        "flops_reconciliation": recon,
        "ops": ops,
        "regions": regions,
        "hotspots": hotspots,
    }
    _last = rep
    from . import emit, enabled as _mon_enabled, gauge
    if emit_records and _mon_enabled():
        gauge(f"profile.attributed_frac.{label}").set(
            attr["attributed_frac"])
        for h in hotspots:
            emit(kind="hotspot", label=label, rank=h["rank"],
                 region=h["region"], scope_kind=h["scope_kind"],
                 ops=h["ops"], flops=h["flops"], bytes=h["bytes"],
                 est_time_s=h["est_time_s"],
                 headroom_s=h["headroom_s"], bound=h["bound"],
                 mfu=h["mfu"], device_kind=ceil["device_kind"],
                 assumed_roofline=ceil["assumed"])
    return rep


def last_report():
    """The most recent report() result (full ledger), or None."""
    return _last


def last_summary(top_k=5):
    """Compact view of the last report for /snapshot: label, attributed
    fraction, and the top-k hotspot regions."""
    rep = _last
    if rep is None:
        return None
    return {
        "label": rep["label"],
        "ts": rep["ts"],
        "device_kind": rep["ceilings"]["device_kind"],
        "assumed_roofline": rep["ceilings"]["assumed"],
        "attributed_frac": round(rep["attributed_frac"], 4),
        "total_flops": rep["total_flops"],
        "hotspots": [
            {"rank": h["rank"], "region": h["region"],
             "bound": h["bound"], "flops": h["flops"],
             "est_time_s": h["est_time_s"],
             "headroom_s": h["headroom_s"]}
            for h in rep["hotspots"][:top_k]
        ],
    }


def _fmt_num(v):
    if v is None:
        return "n/a"
    for unit, scale in (("G", 1e9), ("M", 1e6), ("K", 1e3)):
        if abs(v) >= scale:
            return f"{v / scale:.2f}{unit}"
    return f"{v:.0f}"


def _fmt_time(v):
    if v is None:
        return "n/a"
    if v >= 1e-3:
        return f"{v * 1e3:.3f}ms"
    return f"{v * 1e6:.2f}us"


def format_table(rep, top_k=10):
    """Human-readable fusion menu for a report() dict."""
    if not rep:
        return "profile: no captured executable"
    c = rep["ceilings"]
    lines = [
        f"profile: {rep['label'] or '<hlo>'}  "
        f"[{c['device_kind']}  peak {_fmt_num(c['peak_flops'])}F/s  "
        f"hbm {_fmt_num(c['hbm_bytes_per_sec'])}B/s"
        f"{'  (assumed)' if c['assumed'] else ''}]",
        f"  flops {_fmt_num(rep['total_flops'])} "
        f"(attributed {rep['attributed_frac']:.1%}"
        + (f", xla recon {rep['flops_reconciliation']:.3f}"
           if rep.get("flops_reconciliation") else "") + ")",
        "",
        f"  {'#':>2} {'region':<40} {'bound':<7} {'flops':>9} "
        f"{'bytes':>9} {'AI':>7} {'est':>10} {'headroom':>10} {'mfu':>6}",
    ]
    for h in rep["hotspots"][:top_k]:
        ai = (h["flops"] / h["bytes"]) if h["bytes"] else None
        ai_s = f"{ai:.2f}" if ai is not None else "n/a"
        mfu_s = f"{h['mfu']:.1%}" if h["mfu"] is not None else "n/a"
        lines.append(
            f"  {h['rank']:>2} {h['region'][:40]:<40} {h['bound']:<7} "
            f"{_fmt_num(h['flops']):>9} {_fmt_num(h['bytes']):>9} "
            f"{ai_s:>7} {_fmt_time(h['est_time_s']):>10} "
            f"{_fmt_time(h['headroom_s']):>10} {mfu_s:>6}")
    return "\n".join(lines)
