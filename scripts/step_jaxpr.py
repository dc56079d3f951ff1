"""Does a change leave a cell's training step the program it was?

    python3 scripts/step_jaxpr.py --root <checkout> --workload <cell> --out <file>

Writes the jaxpr of the cell's ``jit.to_static`` step, traced at the cell's
own configuration and traffic with the kernels on (every ``pallas_call``'s
body, grid, blocks and compiler parameters are in the text) and closures'
addresses taken out. Run it on the parent's checkout and on the change's
and ``cmp`` the two files: equal text is the same program handed to XLA,
so no device time, kernel or kernel count of that cell can have moved
(PERF.md section 6, PR 47: the seven cells whose steps share code with the
keye cell). Off the chip: nothing compiles or runs; a step of 0.5 B
parameters takes 7 GB of host memory and half a minute.
"""
import argparse
import importlib
import json
import os
import re
import sys


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    sys.path.insert(0, os.path.abspath(args.root))
    os.chdir(args.root)

    import jax
    import numpy as np
    import paddle_tpu as pt
    from paddle_tpu import jit
    from paddle_tpu.ops import pallas
    from benchmark.families import trainer

    pallas.interpret_mode = lambda: False       # the chip's kernels
    config, traffic = args.workload.split(".")
    with open(f"benchmark/configs/{config}.json") as f:
        cfg = json.load(f)
    with open(f"benchmark/traffic/{traffic}.json") as f:
        traffic = dict(json.load(f), chips=1)

    class Traced(Exception):
        pass

    make = jit.StaticFunction._make_entry

    def make_entry(self, *a, **kw):
        entry = make(self, *a, **kw)
        jitted = entry["jitted"]

        def trace_only(state, arrays):
            shapes = jax.tree_util.tree_map(
                lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype),
                (state, arrays))
            text = str(jax.make_jaxpr(jitted)(*shapes))
            with open(out, "w") as f:
                f.write(re.sub(r" at 0x[0-9a-f]+>", ">", text))
            raise Traced()
        entry["jitted"] = trace_only
        return entry

    jit.StaticFunction._make_entry = make_entry
    # the seed's weights are not needed to trace
    trainer.Trainer.load = lambda self, weights: None
    family = importlib.import_module("benchmark.families." + cfg["family"])
    step = family.build(cfg, traffic, None).step
    batch = family.host_batch(cfg, traffic, np.random.default_rng(0))
    try:
        step(*[pt.to_tensor(b) for b in batch])
    except Traced:
        print(args.workload, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main()
