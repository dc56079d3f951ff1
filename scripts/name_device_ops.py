"""What each of a cell's largest device operations IS: the traced step's
instructions by their own time, each with the layers and ``F.*`` scopes
that ``monitor.profile.instruction_ledger()`` says it holds (ROADMAP A6):

    chiprun -- python3 scripts/name_device_ops.py \
        --workload sdar_30b_a3b_chat.block_diffusion_8k --seed 3000045001 \
        [--seconds 20] [--top 40] [--root <another checkout>] [--tag parent]

Runs ``benchmark/run.py --trace 1`` of ``--root`` (default: this checkout)
in this process, so the executables ``monitor.xla`` kept are still there
when the run ends, then joins the run's ``.xplane.pb`` with the ledger as
``benchmark/program_trace.py`` does, but keeps every instruction apart:
own ms a traced step, the name the driver's ``breakdown.device_ops`` gives
it (base name and result types), its operand types, its phase and regions.
Instructions with one name, one signature and one set of regions are
added up. The table goes to ``chiprun_out/ops/<tag>.<cell>.txt`` and its
first ``--top`` lines to standard output, with the kernel counters
(``monitor.snapshot`` of ``qk_heads``, ``mla_heads``, ``flash_attention``,
``moe_experts``) behind them. **Read no set-up number from such a run**: under
``runpy`` a step traces and lowers 1.7 x as slowly (PERF.md section 6, PR 41).
"""
import argparse
import os
import re
import runpy
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_INSTANCE = re.compile(r"_\d+(?=/|$)")
COUNTERS = ("qk_heads", "mla_heads", "flash_attention", "moe_experts")


def table(root, cell, top):
    """[(ms a step, calls a step, base name, signature, regions)] of the
    traced run that just ended under ``root``, largest first, and the
    number of traced steps."""
    from jax.profiler import ProfileData
    from benchmark import program_trace, reduce_trace
    path = reduce_trace.find_trace(
        os.path.join(root, ".benchmark_work", "trace", cell))
    profile = ProfileData.from_file(path)
    planes = sorted((p for p in profile.planes
                     if reduce_trace.DEVICE_PLANE.match(p.name)),
                    key=lambda p: p.name)
    lines = {ln.name: ln for ln in planes[0].lines}
    text, calls = {}, {}
    for ev in lines[reduce_trace.OPS_LINE].events:
        name = program_trace.instruction_name(ev.name)
        text.setdefault(name, ev.name)
        calls[name] = calls.get(name, 0) + 1
    own, _ = program_trace.device_times(profile)
    step = max({m for m, _ in own if m}, key=lambda m: sum(
        s for (mod, _), s in own.items() if mod == m))
    steps = sum(program_trace.module_name(ev.name) == step
                for ev in lines[reduce_trace.MODULES_LINE].events)
    rows = program_trace.ledger() or {}
    seen = {}
    for (module, name), sec in own.items():
        if module != step:
            continue
        row = rows.get((module, name))
        parts = sorted({(p["phase"], _INSTANCE.sub("_*", p["region"]))
                        for p in row["parts"]}) if row else []
        if row and row.get("kernel"):
            parts = [("kernel", row["kernel"])] + parts
        key = (reduce_trace.op_key(text[name]),
               reduce_trace.op_signature(text[name]), tuple(parts))
        rec = seen.setdefault(key, [0.0, 0])
        rec[0] += sec
        rec[1] += calls[name]
    out = sorted(((1e3 * sec / steps, n / steps) + key
                  for key, (sec, n) in seen.items()), reverse=True)
    return out[:top], steps


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--root", default=HERE)
    ap.add_argument("--tag", default="change")
    args = ap.parse_args(argv)
    root = os.path.abspath(args.root)
    sys.path.insert(0, root)
    sys.argv = [os.path.join(root, "benchmark", "run.py"),
                "--workload", args.workload, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"]
    os.chdir(root)
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as e:
        if e.code:
            raise
    found, steps = table(root, args.workload, 4 * args.top)
    lines = [f"# {args.tag} {args.workload} seed {args.seed}: own ms a step "
             f"over {steps} traced steps | calls a step | name | signature "
             f"| phase:region ..."]
    for ms, n, name, signature, parts in found:
        lines.append(f"{ms:9.3f} | {n:6.1f} | {name} | {signature[:300]} | "
                     + " ; ".join(f"{ph}:{reg}" for ph, reg in parts))
    from paddle_tpu import monitor
    for prefix in COUNTERS:
        lines.append(f"# counters {prefix}: {monitor.snapshot(prefix)}")
    out = os.path.join(HERE, "chiprun_out", "ops")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{args.tag}.{args.workload}.txt"), "w") as f:
        f.write("\n".join(lines) + "\n")
    print("\n".join(lines[:1 + args.top] + lines[-len(COUNTERS):]),
          flush=True)


if __name__ == "__main__":
    main()
