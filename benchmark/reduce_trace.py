"""From a profiler trace (``.xplane.pb``) to a summary the per-layer
readers take their numbers from. Read with ``jax.profiler.ProfileData``
and nothing else.

What a TPU trace looks like (seen by hand, PR 24; PERF.md section 5): one
plane ``/device:TPU:<n>`` per chip. Its line ``XLA Ops`` holds one event per
executed HLO instruction, named by the instruction's whole text
(``%fusion.9 = f32[64,128,30522]{...} fusion(...), kind=kLoop, ...``);
control-flow parents enclose their bodies' events. ``XLA Modules`` holds one
event per executed program, ``Steps`` one per step; ``Async XLA Ops`` holds
the copies XLA overlaps with compute, which are not counted as busy. A
Pallas (Mosaic) kernel is a ``custom-call`` whose text carries
``custom_call_target="tpu_custom_call"``; the kernels have no names of their
own (all are ``%jvp__.N`` or the like), only their shapes tell them apart.
The host's threads are lines of the plane ``/host:CPU``, and the
benchmark's own spans (``bench.feed``, ``bench.dispatch``, ``bench.fetch``)
lie on the line of the thread that drove the loop. All on one clock.

    python3 benchmark/reduce_trace.py <dir-or-file>      # print what is there
"""
import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_SUFFIX = re.compile(r"\.\d+$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OPERAND_NAME = re.compile(r" ?%[\w.\-]+")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


def op_base(name):
    """An instruction's name as the trace prints it, ``.N`` stripped."""
    return _SUFFIX.sub("", name.split(" = ")[0].lstrip("%"))


def op_signature(name):
    """``result-types opcode(operand-types)`` of an instruction's text, with
    layouts and operand names taken out; the text itself where it has no
    such form."""
    if " = " not in name:
        return name
    text = _LAYOUT.sub("", name.split(" = ", 1)[1])
    m = _OPCODE.search(" " + text)
    if m is None:
        return text
    depth, end = 0, len(text)
    for i in range(m.end() - 1, len(text)):       # m is offset by the " "
        depth += {"(": 1, ")": -1}.get(text[i - 1], 0)
        if depth == 0 and i > m.end() - 1:
            end = i - 1
            break
    return _OPERAND_NAME.sub("", text[:end + 1])


def op_key(name):
    """What the breakdown calls an operation: its base name and result
    types, so that the same operation of every layer and step adds up."""
    sig = op_signature(name)
    m = _OPCODE.search(" " + sig)
    result = sig[:m.start()] if m else ""
    return f"{op_base(name)} {result}".strip()[:120]


def union_seconds(intervals):
    """Length of the union of (start, end) intervals, and the gaps between
    its pieces as (start, end)."""
    busy, gaps, cur_s, cur_e = 0.0, [], None, None
    for s, e in sorted(intervals):
        if cur_e is None:
            cur_s, cur_e = s, e
        elif s <= cur_e:
            cur_e = max(cur_e, e)
        else:
            busy += cur_e - cur_s
            gaps.append((cur_e, s))
            cur_s, cur_e = s, e
    if cur_e is not None:
        busy += cur_e - cur_s
    return busy, gaps


def self_times(events):
    """(name, self seconds) of events of one line that may enclose each
    other: an event's own time is its length less what its children
    cover."""
    out, stack = [], []   # stack of [name, end, self]
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and s >= stack[-1][1]:
            done = stack.pop()
            out.append((done[0], done[2]))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    while stack:
        done = stack.pop()
        out.append((done[0], done[2]))
    return out


def _events(line):
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def _label_gap(gap, modules, spans, ops):
    """What the host was doing in an idle gap: inside an executing program
    the device is waiting on itself; between programs, the benchmark span
    that covers most of the gap."""
    s, e = gap
    for _, ms, me in modules:
        if ms <= s and e <= me:
            before = max((o for o in ops if o[2] <= s + 1e-12),
                         key=lambda o: o[2], default=None)
            return "in_step_after:" + (op_base(before[0]) if before else "?")
    best, cover = "host:no_span", 0.0
    for name, ss, se in spans:
        c = min(e, se) - max(s, ss)
        if c > cover:
            best, cover = "host:" + name, c
    return best


def reduce(profile):
    """The summary of one trace."""
    planes = list(profile.planes)
    devices = sorted((p for p in planes if DEVICE_PLANE.match(p.name)),
                     key=lambda p: p.name)
    if not devices:
        raise ValueError("the trace has no device plane: " +
                         ", ".join(p.name for p in planes))
    spans = []
    for p in planes:
        if p.name == HOST_PLANE:
            for line in p.lines:
                spans += [ev for ev in _events(line)
                          if ev[0].startswith(SPAN_PREFIX)]
    per_device, first = [], None
    for p in devices:
        lines = {ln.name: ln for ln in p.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{p.name} has no line {OPS_LINE!r}: "
                             f"{sorted(lines)}")
        ops = _events(lines[OPS_LINE])
        if not ops:
            raise ValueError(f"no operation ran on {p.name} in the trace")
        modules = _events(lines[MODULES_LINE]) if MODULES_LINE in lines else []
        busy, gaps = union_seconds([(s, e) for _, s, e in ops])
        window = max(e for _, _, e in ops) - min(s for _, s, _ in ops)
        dev = {"name": p.name, "busy_s": busy, "window_s": window,
               "ops": ops, "modules": modules, "gaps": gaps}
        per_device.append(dev)
        first = first or dev
    totals, kernels = {}, []
    for name, sec in self_times(first["ops"]):
        key = op_key(name)
        totals[key] = totals.get(key, 0.0) + sec
        if MOSAIC_TARGET in name:
            kernels.append((op_signature(name), sec))
    top = sorted(totals.items(), key=lambda kv: -kv[1])
    gaps = sorted(first["gaps"], key=lambda g: g[0] - g[1])[:10]
    return {
        "devices": [d["name"] for d in per_device],
        "busy_s": sum(d["busy_s"] for d in per_device) / len(per_device),
        "window_s": sum(d["window_s"] for d in per_device) / len(per_device),
        "op_self_s": totals,
        "kernels": kernels,
        "top_ops": [[k, v] for k, v in top],
        "idle_gaps": [[_label_gap(g, first["modules"], spans, first["ops"]),
                       g[1] - g[0]] for g in gaps],
        "modules": first["modules"],
        "spans": spans,
    }


def find_trace(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def reduce_dir(path):
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_trace(path)))


def describe(path, out=sys.stdout):
    """Planes, lines, and the commonest events with their stats: what to
    look at by hand before trusting a reduction."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(find_trace(path))
    for p in profile.planes:
        print(f"PLANE {p.name!r}", file=out)
        for line in p.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r} events={len(events)}", file=out)
            seen = {}
            for ev in events:
                rec = seen.setdefault(op_base(ev.name), [0, 0.0, None])
                rec[0] += 1
                rec[1] += ev.duration_ns
                rec[2] = rec[2] or ev
            for name, (n, ns, ev) in sorted(seen.items(),
                                            key=lambda kv: -kv[1][1])[:40]:
                stats = {k: (str(v)[:120]) for k, v in ev.stats}
                print(f"    {name!r} n={n} total_ms={ns / 1e6:.3f} "
                      f"stats={stats}", file=out)


if __name__ == "__main__":
    describe(sys.argv[1])
