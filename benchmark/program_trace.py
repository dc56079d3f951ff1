"""A traced run seen through the program's own labels: device time by phase
(forward, backward, optimizer) and by model part, the time of each Pallas
kernel by its name, and the program's spans on the host.

Two sides are joined here. The clock's: the run's ``.xplane.pb``, opened
here (``.benchmark_work/trace/<cell>``), gives every executed instruction's
own time on the first device, by HLO module and instruction name, and the
host's events by name. The program's: ``paddle_tpu.monitor.profile.
instruction_ledger()`` says, from the optimized HLO of the executables the
monitor kept, which phase and which region each instruction holds and, for
an instruction that holds several, the modelled flops and bytes of each
part. An instruction's own time goes to its phase; one that holds several
phases is split in proportion to its parts' modelled time (the larger of
flops / peak flops and bytes / peak bandwidth) and its whole time is also
counted as cross-phase: that much of the split rests on the cost model and
not on the clock. A phase whose parts in an instruction have no modelled
cost at all (a conversion the compiler moved there) is not held by it. An
instruction the ledger does not know is phase ``none``.

Every reader returns ``None`` where there is nothing to read: no trace, a
trace without a device plane (the CPU rehearsal), a program that has no
``instruction_ledger`` or kept no executable, no span of the name.

    python3 benchmark/program_trace.py <trace dir or file>   # host spans only
"""
import os
import re
import sys

from benchmark import reduce_trace, stats

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_ROOT = os.path.join(ROOT, ".benchmark_work", "trace")
PHASES = ("fwd", "bwd", "opt", "none")
UNKNOWN = "<not in the ledger>"
STEP_SPANS = ("jit.collect", "jit.execute", "jit.writeback")
ENCLOSING_SPAN = "bench.dispatch"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_INSTANCE = re.compile(r"_\d+(?=/|$)")

_cache = {}       # trace file -> what was read from it


def instruction_name(text):
    """``fusion.123`` of an event named by the instruction's whole text."""
    return text.split(" = ")[0].strip().lstrip("%")


def module_name(text):
    """``jit_bert_step`` of the module event ``jit_bert_step(1680...)``."""
    return _FINGERPRINT.sub("", text.strip())


def device_times(profile):
    """({(module, instruction): own seconds}, busy seconds) of the first
    device, or None where the trace has no device plane."""
    planes = sorted((p for p in profile.planes
                     if reduce_trace.DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)
    if not planes:
        return None
    lines = {ln.name: ln for ln in planes[0].lines}
    if reduce_trace.OPS_LINE not in lines:
        return None
    modules = sorted(
        ((ev.start_ns, ev.start_ns + ev.duration_ns, module_name(ev.name))
         for ev in lines[reduce_trace.MODULES_LINE].events)
        if reduce_trace.MODULES_LINE in lines else ())
    events, at = [], 0
    for ev in sorted(lines[reduce_trace.OPS_LINE].events,
                     key=lambda ev: ev.start_ns):
        while at < len(modules) and modules[at][1] < ev.start_ns:
            at += 1
        inside = at < len(modules) and modules[at][0] <= ev.start_ns
        events.append(((modules[at][2] if inside else None,
                        instruction_name(ev.name)),
                       ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9))
    if not events:
        return None
    own = {}
    for key, sec in reduce_trace.self_times(events):
        own[key] = own.get(key, 0.0) + sec
    busy, _ = reduce_trace.union_seconds([(s, e) for _, s, e in events])
    return own, busy


def host_events(profile, names):
    """[(name, start s, end s, thread)] of the host's events of the given
    names, every thread of ``/host:CPU``."""
    out = []
    for plane in profile.planes:
        if plane.name != reduce_trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, ev.start_ns * 1e-9,
                                (ev.start_ns + ev.duration_ns) * 1e-9,
                                line.name))
    return out


def ledger():
    """{(module, instruction): row} from the program, or None where the
    program has no such function or kept no executable."""
    try:
        from paddle_tpu.monitor import profile
    except ImportError:
        return None
    build = getattr(profile, "instruction_ledger", None)
    if build is None:
        return None
    rows = build()
    return {(r["module"], r["name"]): r for r in rows} or None


def modelled_seconds(part, peaks):
    return max(part["flops"] / peaks["bf16_flops_per_s"],
               part["bytes"] / peaks["hbm_bytes_per_s"])


def shares(row, peaks):
    """([(phase, region, share of the instruction's time)], phases held)."""
    parts = [(p["phase"], p["region"], modelled_seconds(p, peaks))
             for p in row["parts"]]
    total = sum(t for _, _, t in parts)
    if total > 0:
        parts = [(ph, reg, t / total) for ph, reg, t in parts if t > 0]
    else:
        parts = [(ph, reg, 1.0 / len(parts)) for ph, reg, _ in parts]
    return parts, {ph for ph, _, _ in parts}


def join(own, rows, peaks):
    """Own device seconds by phase, by (phase, region) and by kernel name,
    and the instructions that hold several phases."""
    phase_s = dict.fromkeys(PHASES, 0.0)
    regions, kernels, served, cross = {}, {}, 0.0, {}
    for key, sec in own.items():
        row = rows.get(key)
        if row is None:
            phase_s["none"] += sec
            regions[("none", UNKNOWN)] = regions.get(("none", UNKNOWN), 0.0) \
                + sec
            continue
        parts, held = shares(row, peaks)
        for phase, region, share in parts:
            phase_s[phase] += sec * share
            regions[(phase, region)] = regions.get((phase, region), 0.0) \
                + sec * share
        if len(held) > 1:
            split = {}
            for phase, _, share in parts:
                split[phase] = split.get(phase, 0.0) + share
            seen = cross.setdefault(reduce_trace.op_base(key[1]),
                                    [0.0, dict.fromkeys(split, 0.0)])
            seen[0] += sec
            for phase, share in split.items():
                seen[1][phase] = seen[1].get(phase, 0.0) + sec * share
        if row.get("kernel"):
            kernels[row["kernel"]] = kernels.get(row["kernel"], 0.0) + sec
        if row.get("serves"):
            served += sec
    return {"phase_s": phase_s, "regions": regions, "kernel_s": kernels,
            "cross": cross, "cross_s": sum(v[0] for v in cross.values()),
            "served_s": served}


def _read(context):
    """What the run's trace file holds, read once: the device's own times
    and the host's spans."""
    try:
        path = reduce_trace.find_trace(
            os.path.join(TRACE_ROOT, context["cell"]["name"]))
    except (FileNotFoundError, KeyError, TypeError):
        return None
    if path not in _cache:
        from jax.profiler import ProfileData
        profile = ProfileData.from_file(path)
        _cache[path] = {
            "device": device_times(profile),
            "host": host_events(profile, STEP_SPANS + (ENCLOSING_SPAN,)),
        }
    return _cache[path]


def phases(summary, context, say=print):
    """The join of one traced run, per step, or None. Prints its note
    lines the first time."""
    seen = _read(context)
    steps = summary.get("steps")
    if seen is None or seen["device"] is None or not steps:
        return None
    if "phases" not in seen:
        rows = ledger()
        if rows is None:
            seen["phases"] = None
            return None
        own, busy = seen["device"]
        out = join(own, rows, summary["peaks"])
        out["busy_s"], out["steps"] = busy, steps
        seen["phases"] = out
        for line in notes(out):
            say(line)
    return seen["phases"]


def model_parts(regions):
    """Regions added up over the instances of a layer class:
    ``.../TransformerEncoderLayer_3/Linear_14`` and its eleven siblings
    are one part of the model, ``.../TransformerEncoderLayer_*/Linear_*``."""
    parts = {}
    for (phase, region), sec in regions.items():
        key = (phase, _INSTANCE.sub("_*", region))
        parts[key] = parts.get(key, 0.0) + sec
    return parts


def notes(out):
    """The note lines of a join: the phases against the busy time they
    have to add up to, the largest regions, the largest cross-phase
    instructions with their modelled split."""
    ms = 1e3 / out["steps"]
    p = out["phase_s"]
    total = sum(p.values())
    gap = abs(total - out["busy_s"]) / out["busy_s"] if out["busy_s"] else 0.0
    lines = [
        "[phases] " + " ".join(f"{k}={p[k] * ms:.3f}" for k in PHASES)
        + f" cross={out['cross_s'] * ms:.3f} busy={out['busy_s'] * ms:.3f}"
        f" compiler_made={out['served_s'] * ms:.3f} ms_per_step"
        f" sum_vs_busy={100 * gap:.3f}%"
        + ("" if gap <= 0.005 else " PHASES_DO_NOT_ADD_UP")]
    for (phase, region), sec in sorted(model_parts(out["regions"]).items(),
                                       key=lambda kv: -kv[1])[:10]:
        lines.append(f"[phases] region {phase} {sec * ms:.3f} ms {region}")
    for name, (sec, split) in sorted(out["cross"].items(),
                                     key=lambda kv: -kv[1][0])[:10]:
        lines.append(
            f"[phases] cross {name} {sec * ms:.3f} ms modelled_split "
            + " ".join(f"{k}={v * ms:.3f}" for k, v in sorted(split.items())))
    for name, sec in sorted(out["kernel_s"].items(), key=lambda kv: -kv[1]):
        lines.append(f"[phases] kernel {name} {sec * ms:.3f} ms")
    return lines


def phase_ms(summary, context, phase):
    out = phases(summary, context)
    return None if out is None else 1e3 * out["phase_s"][phase] / out["steps"]


def kernel_ms(summary, context, prefix):
    """Own device ms per step of the Pallas kernels whose ``name=`` starts
    with ``prefix``; None where the step holds none."""
    out = phases(summary, context)
    if out is None:
        return None
    sec = [s for name, s in out["kernel_s"].items() if name.startswith(prefix)]
    return 1e3 * sum(sec) / out["steps"] if sec else None


def in_the_loop(events, name):
    """The spans of one name that lie inside a ``bench.dispatch`` span of
    their own thread: those of the traced steps. Every span of the name
    where the trace holds no ``bench.dispatch`` at all."""
    outer = [e for e in events if e[0] == ENCLOSING_SPAN]
    mine = [e for e in events if e[0] == name]
    if not outer:
        return mine
    return [e for e in mine
            if any(o[3] == e[3] and o[1] <= e[1] and e[2] <= o[2]
                   for o in outer)]


def span_report(events):
    """How the program's step spans lie in the benchmark's: for each name
    its count, how many lie inside a ``bench.dispatch`` span of their own
    thread, and the share of the enclosing spans' time they cover."""
    report = {}
    for name in STEP_SPANS:
        inside = in_the_loop(events, name)
        report[name] = {"count": sum(1 for e in events if e[0] == name),
                        "inside": len(inside),
                        "seconds_inside": sum(e[2] - e[1] for e in inside)}
    report["enclosing_s"] = sum(e[2] - e[1] for e in events
                                if e[0] == ENCLOSING_SPAN)
    return report


def span_ms(context, name, say=print):
    """Median duration in ms of the host's spans of one name in the
    traced steps, or None where the trace holds none."""
    seen = _read(context)
    if seen is None:
        return None
    if "spans" not in seen:
        seen["spans"] = span_report(seen["host"])
        r = seen["spans"]
        if any(r[n]["count"] for n in STEP_SPANS):
            covered = sum(r[n]["seconds_inside"] for n in STEP_SPANS)
            say("[spans] " + " ".join(
                f"{n}={r[n]['inside']}/{r[n]['count']}_inside"
                for n in STEP_SPANS)
                + f" share_of_{ENCLOSING_SPAN}="
                + (f"{100 * covered / r['enclosing_s']:.1f}%"
                   if r["enclosing_s"] else "n/a"))
    durations = [e[2] - e[1] for e in in_the_loop(seen["host"], name)]
    return 1e3 * stats.percentile(durations, 50) if durations else None


if __name__ == "__main__":
    from jax.profiler import ProfileData
    _events = host_events(
        ProfileData.from_file(reduce_trace.find_trace(sys.argv[1])),
        STEP_SPANS + (ENCLOSING_SPAN,))
    print(span_report(_events))
