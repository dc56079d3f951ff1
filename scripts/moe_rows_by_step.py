"""How many rows a cell's held experts compute, step by step, and whether
that drifts inside a run or moves with the seed:

    chiprun -- python3 scripts/moe_rows_by_step.py \
        --workload lfm2_8b_a1b.causal_pretrain_2x8k --seeds 1,2,3 \
        [--steps 74] [--pools 8,64] [--out chiprun_out/rows.json]

One trainer, put back to each seed's fresh state (``benchmark/control.py``'s
way), driven for ``--steps`` steps of the cell's own loop — a run's 8
steps of set-up and a window's worth — with the device counters ``moe.*``
read after every step. For each pool size (the traffic file's
``pool_size`` replaced: how many distinct batches the loop cycles) and
seed: the mean of ``moe.rows_computed`` a step over the whole stretch, over
its first and its last ten steps, its smallest and largest step, the
padding factor, and the fullest expert's rows (a mean over the expert
layers) at the first and the last step. A step's expert products follow its computed rows (PERF.md
section 6, PR 27), so the spread of the stretch means over the seeds is the
part of ``step_ms``'s spread that the experts' ladder makes. Counts, no
times: it runs anywhere, slowly on a CPU. Not run by the benchmark's runs.
"""
import argparse
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness          # noqa: E402


def rows_by_step(trainer, pool, steps):
    """[(rows computed, slots routed here, the fullest expert's rows added
    up over the expert layers, the expert layers)] of each of ``steps``
    steps."""
    import paddle_tpu as pt
    from paddle_tpu.monitor import device_counters
    seen, out = device_counters.read("moe."), []
    for i in range(steps):
        trainer.step(*[pt.to_tensor(a) for a in pool[i % len(pool)]])
        now = device_counters.read("moe.")
        out.append(tuple(now[k] - seen.get(k, 0)
                         for k in ("moe.rows_computed",
                                   "moe.slots_routed_here",
                                   "moe.expert_load_max", "moe.steps")))
        seen = now
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=74)
    ap.add_argument("--pools", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",") if s]

    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, cfg, traffic = harness.resolve(manifest, args.workload)
    pools = [int(p) for p in args.pools.split(",") if p] \
        or [traffic["pool_size"]]
    import paddle_tpu as pt
    pt.device.enable_compilation_cache(min_compile_time_secs=0.0)
    job = harness.load_module("jobs", traffic["job"])
    family = harness.load_module("families", cfg["family"])
    traffic = dict(traffic, chips=cell["chips"])
    trainer, rows = None, {}
    for pool_size in pools:
        means = []
        for seed in seeds:
            weights = family.reference.init_weights(cfg, seed)
            if trainer is None:
                trainer = family.build(cfg, traffic, weights)
            else:
                trainer.reset(weights)
            del weights
            pool = job.make_pool(family, cfg,
                                 dict(traffic, pool_size=pool_size), seed)
            steps = rows_by_step(trainer, pool, args.steps)
            computed = [r for r, _, _, _ in steps]
            rows[f"{pool_size}/{seed}"] = steps
            means.append(statistics.mean(computed))
            harness.say(
                "rows", pool=pool_size, seed=seed, mean=f"{means[-1]:.0f}",
                first10=f"{statistics.mean(computed[:10]):.0f}",
                last10=f"{statistics.mean(computed[-10:]):.0f}",
                smallest=min(computed), largest=max(computed),
                padding=f"{sum(computed) / sum(s[1] for s in steps):.3f}",
                fullest_first=steps[0][2] // steps[0][3],
                fullest_last=steps[-1][2] // steps[-1][3])
        if len(means) > 1:
            q1, _, q3 = statistics.quantiles(means, n=4)
            harness.say(
                "rows", pool=pool_size, seeds=len(means),
                median_of_means=f"{statistics.median(means):.0f}",
                quartile_spread_pct=f"{100 * (q3 - q1) / statistics.median(means):.3f}",
                range_pct=f"{100 * (max(means) - min(means)) / statistics.median(means):.3f}")
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "steps": args.steps,
                       "rows": rows}, f)


if __name__ == "__main__":
    main()
