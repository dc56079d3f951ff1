"""python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, once. Everything that belongs to one cell is found
by the names in BENCHMARK.json (benchmark/README.md lists the file names).
Earlier lines are notes; the last line of standard output is the one JSON
object of the result. With ``--trace 0`` its metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Fails, with no result line, where JAX finds no TPU, a device kind that
benchmark/peaks.json does not know, or another number of chips than the
cell asks for.
"""
import time

_T_PROCESS = time.perf_counter()

import argparse          # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK_DIR = os.path.join(ROOT, ".benchmark_work")


def say(phase, **fields):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark/{kind}/{name}.py does not exist")
    if f"benchmark.{kind}.{name}" in sys.modules:
        return sys.modules[f"benchmark.{kind}.{name}"]
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_json(path):
    with open(path) as f:
        return json.load(f)


def applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(manifest, workload):
    """(cell, configuration, traffic) of a workload's name."""
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, cfg, traffic


def cell_limits(cell):
    """The limits of `correct` for one cell, from a file of its own that
    keeps the readings they were set from beside them. A cell inherits
    none: its limits come from its own readings (benchmark/control.py)."""
    path = os.path.join(HERE, "limits", cell["name"] + ".json")
    if not os.path.isfile(path):
        raise SystemExit(f"benchmark/limits/{cell['name']}.json does not "
                         f"exist")
    return load_json(path)["limits"]


def check_device(cell):
    """The device as JAX reports it, or an exit without a result."""
    import jax
    from benchmark import kernel_costs

    devices = jax.devices()
    d = devices[0]
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devices))
    if d.platform != "tpu":
        raise SystemExit(f"the benchmark needs a TPU; JAX found "
                         f"{d.platform!r} ({d.device_kind!r})")
    if len(devices) != cell["chips"]:
        raise SystemExit(f"{cell['name']} asks for {cell['chips']} chip(s) "
                         f"and JAX found {len(devices)}")
    try:
        peaks = kernel_costs.peaks_for_kind(d.device_kind)
    except KeyError as e:
        raise SystemExit(e.args[0])
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}, peaks


def run_cell(manifest, cell, cfg, traffic, limits, seed, seconds, trace,
             device, peaks, t_process=None):
    """Drive one cell and build the result object. ``main`` has looked for
    the chip; the tests call this with a tiny size on the CPU."""
    t_process = time.perf_counter() if t_process is None else t_process
    job = load_module("jobs", traffic["job"])
    family = load_module("families", cfg["family"])
    trace_dir = None
    if trace:
        trace_dir = os.path.join(WORK_DIR, "trace", cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
    seen = job.run(cell, cfg, traffic, limits, family, seed, seconds,
                   trace_dir, time.perf_counter, say)
    setup_s = seen["t_window"] - t_process
    say("result", setup_s=f"{setup_s:.1f}", attempted=seen["attempted"],
        failed=seen["failed"], correct=seen["correct"],
        mfu=f"{seen['flops_per_s_chip'] / peaks['bf16_flops_per_s']:.4f}",
        peak_hbm_share=f"{seen['memory_peak_bytes'] / peaks['hbm_bytes']:.3f}")
    device = dict(device, memory_peak_bytes=seen["memory_peak_bytes"])
    result = {"correct": seen["correct"], "attempted": seen["attempted"],
              "failed": seen["failed"], "metrics": {}, "device": device}
    units = {m["name"]: m["unit"]
             for m in manifest["end_to_end"] + manifest["per_layer"]}

    if not trace:
        values = dict(seen["end_to_end"], setup_s=setup_s)
        for m in manifest["end_to_end"]:
            if applies(m, cell["name"]):
                if m["name"] not in values:
                    raise SystemExit(f"{cell['name']} has to report "
                                     f"{m['name']} and its job gave none")
                result["metrics"][m["name"]] = {
                    "value": values[m["name"]], "unit": units[m["name"]]}
        return result

    from benchmark import reduce_trace
    summary = reduce_trace.reduce_dir(trace_dir)
    summary["steps"] = seen["counters"]["traced_steps"]
    summary["peaks"] = peaks
    context = {"cell": cell, "config": cfg, "traffic": traffic}
    for m in manifest["per_layer"]:
        if applies(m, cell["name"]):
            value = load_module("layer_metrics", m["name"]).read(
                summary, seen["counters"], context)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": units[m["name"]]}
    device["busy_s"] = summary["busy_s"]
    device["window_s"] = summary["window_s"]
    result["breakdown"] = {"device_ops": summary["top_ops"][:10],
                           "idle_gaps": summary["idle_gaps"][:10]}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic = resolve(manifest, args.workload)
    limits = cell_limits(cell)
    device, peaks = check_device(cell)
    import paddle_tpu as pt
    say("device", compile_cache=pt.device.enable_compilation_cache(
        min_compile_time_secs=0.0))
    result = run_cell(manifest, cell, cfg, traffic, limits, args.seed,
                      args.seconds, bool(args.trace), device, peaks,
                      _T_PROCESS)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
