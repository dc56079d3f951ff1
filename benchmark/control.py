"""The readings a cell's correctness limits are set from, in one process.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3,... \
        [--control-seeds 1,2,3] [--out chiprun_out/control.json]

For every seed: the program's numbers (one trainer, put back to each
seed's fresh state, driven through the steps the reference follows at the
cell's own size) against the float32 reference. For every control seed:
the reference computed in the nearest lower precision (float8 for a
configuration that states bfloat16), put in the program's place. Prints
each number, then for each the program's largest and the control's
smallest: a limit goes between the two, with room on both sides, and a
number that precision hardly moves gets about three times the program's
largest (benchmark/limits/<cell>.json keeps limits and readings; the cell
needs no such file to be read here). ``--out`` also keeps every norm
read, leaf by leaf, so that another measure can be tried without the chip.
Not run by the benchmark's runs.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness                 # noqa: E402
from benchmark.reference.common import PRECISIONS    # noqa: E402


def numbers(job, got, want, leaves):
    """{number: value} as `correct` compares them."""
    return {name: value
            for name, value, _, _ in job.numbers_compared(got, want, leaves)}


def program_readings(job, family, cfg, traffic, seeds, say):
    """The program's numbers on each seed, through one compiled step."""
    reference = family.reference
    trainer, got = None, {}
    for seed in seeds:
        weights = reference.init_weights(cfg, seed)
        if trainer is None:
            trainer = family.build(cfg, traffic, weights)
        else:
            trainer.reset(weights)
        del weights
        pool = job.make_pool(family, cfg, traffic, seed)
        t = time.perf_counter()
        got[seed], _ = job.checked_steps(trainer, pool, reference, cfg, seed,
                                         time.perf_counter)
        say("program", seed=seed, seconds=f"{time.perf_counter() - t:.1f}",
            losses=[round(x, 4) for x in got[seed]["loss"]])
    del trainer
    gc.collect()
    return got


def readings(cell, cfg, traffic, seeds, control_seeds, say,
             control_precision=PRECISIONS[1]):
    job = harness.load_module("jobs", traffic["job"])
    family = harness.load_module("families", cfg["family"])
    traffic = dict(traffic, chips=cell["chips"])
    reference, hyper = family.reference, cfg["assumed"]["optimizer"]
    leaves = reference.compared_leaves(cfg)
    got = program_readings(job, family, cfg, traffic, seeds, say)
    rows = {"program": {}, "control": {}}
    raw = {}        # every norm read, for a look at other measures offline
    for seed in sorted(set(seeds) | set(control_seeds)):
        batches = job.make_pool(family, cfg, traffic, seed)[:job.CHECKED_STEPS]
        want = reference.train(cfg, hyper, seed, batches)
        raw[seed] = {"reference": want, "program": got.get(seed)}
        if seed in got:
            rows["program"][seed] = numbers(job, got[seed], want, leaves)
            say("program", seed=seed, **rows["program"][seed])
        if seed in control_seeds:
            ctl = reference.train(cfg, hyper, seed, batches,
                                  precision=control_precision)
            rows["control"][seed] = numbers(job, ctl, want, leaves)
            raw[seed]["control"] = ctl
            say("control", seed=seed, precision=control_precision,
                **rows["control"][seed])
    summary = {}
    for name in next(iter(rows["program"].values()), {}):
        hi = max(r[name] for r in rows["program"].values())
        lo = min((r[name] for r in rows["control"].values()), default=None)
        summary[name] = {"program_largest": hi, "control_smallest": lo}
        say("summary", number=name, **summary[name])
    return {"rows": rows, "summary": summary, "raw": raw}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = [int(s) for s in args.control_seeds.split(",") if s]

    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic = harness.resolve(manifest, args.workload)
    harness.check_device(cell)
    import paddle_tpu as pt
    pt.device.enable_compilation_cache(min_compile_time_secs=0.0)
    out = readings(cell, cfg, traffic, seeds, control_seeds, harness.say)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, **out}, f, indent=1)


if __name__ == "__main__":
    main()
