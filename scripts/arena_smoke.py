"""Flat-parameter-arena smoke gate (tier-1-safe: small MLP, CPU,
seconds).

Trains the SAME model+Adam step twice under ``jit.to_static`` with
profiling scopes armed — once on the per-leaf optimizer path, once with
``flat_arena=True`` — builds the per-op cost ledger for both captured
executables, and asserts:

* the two runs are BIT-IDENTICAL (losses and final params)
* no concatenate / gather / scatter opcodes are attributed to the
  opt.* region in the flat step (the grad pack is ``arena.pack``'s)
* zero extra recompiles: after step 1 the jit cache only ever hits
  (``jit.recompile`` stays flat for the whole run)

Writes the monitor JSONL to --out-dir and prints one JSON result line.
Exit code 0 iff every gate passes.
"""
import argparse
import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import re

import numpy as np

_BANNED_RE = re.compile(r"(concatenate|gather|scatter)\(")


def _opt_rows(rep):
    return [o for o in rep["ops"] if "opt." in (o["region"] or "")]


def _banned_in_opt(hlo_text):
    """concat/gather/scatter instructions (top-level OR inside fusions)
    whose op_name metadata places them in the optimizer scope."""
    return [l.strip()[:160] for l in hlo_text.splitlines()
            if _BANNED_RE.search(l) and "opt." in l]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="/tmp/paddle_tpu_arena_smoke")
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    import paddle_tpu as pt
    from paddle_tpu import jit, monitor, nn, optimizer as opt

    os.makedirs(args.out_dir, exist_ok=True)
    jsonl = monitor.enable(os.path.join(args.out_dir,
                                        "arena_smoke.jsonl"))
    monitor.profile.enable()

    def build():
        pt.seed(0)
        return nn.Sequential(nn.Linear(64, args.hidden), nn.ReLU(),
                             nn.Linear(args.hidden, args.hidden),
                             nn.ReLU(),
                             nn.Linear(args.hidden, 10))

    rng = np.random.RandomState(0)
    xs = [rng.randn(args.batch, 64).astype("f4")
          for _ in range(args.steps)]
    ys = [rng.randn(args.batch, 10).astype("f4")
          for _ in range(args.steps)]

    def train(flat):
        model = build()
        adam = opt.Adam(learning_rate=1e-3,
                        parameters=model.parameters(), flat_arena=flat)

        def body(x, y):
            loss = (model(x) - y).square().mean()
            loss.backward()
            adam.step()
            adam.clear_grad()
            return loss

        # distinct names -> distinct monitor.xla capture labels
        body.__name__ = "step_flat" if flat else "step_base"
        fn = jit.to_static(body, models=[model], optimizers=[adam])
        losses, times = [], []
        for x, y in zip(xs, ys):
            t0 = time.perf_counter()
            losses.append(float(fn(pt.to_tensor(x),
                                   pt.to_tensor(y)).numpy()))
            times.append(time.perf_counter() - t0)
        # step 1 pays the compile
        step_s = sum(times[1:]) / max(1, len(times) - 1)
        params = {k: np.asarray(v.numpy())
                  for k, v in model.state_dict().items()}
        rep = monitor.profile.report(emit_records=False)
        hlo = monitor.xla.executable(None).as_text()
        return losses, params, rep, hlo, step_s

    losses_base, params_base, rep_base, hlo_base, step_base_s = \
        train(flat=False)
    rc0 = monitor.counter("jit.recompile")._value
    c0 = monitor.counter("jit.compile")._value
    losses_flat, params_flat, rep_flat, hlo_flat, step_flat_s = \
        train(flat=True)
    recompiles = monitor.counter("jit.recompile")._value - rc0
    compiles = monitor.counter("jit.compile")._value - c0

    if rep_base is None or rep_flat is None:
        print(json.dumps({"metric": "arena_smoke", "pass": False,
                          "error": "no captured executable"}))
        return 1

    base_rows, flat_rows = _opt_rows(rep_base), _opt_rows(rep_flat)
    opt_bytes_base = sum(o["bytes"] for o in base_rows)
    opt_bytes_flat = sum(o["bytes"] for o in flat_rows)
    reduction = (1.0 - opt_bytes_flat / opt_bytes_base
                 if opt_bytes_base else 0.0)
    base_banned = _banned_in_opt(hlo_base)
    flat_banned = _banned_in_opt(hlo_flat)

    bit_identical = losses_base == losses_flat and all(
        np.array_equal(params_base[k], params_flat[k])
        for k in params_base)

    result = {
        "metric": "arena_smoke",
        "steps": args.steps,
        "opt_bytes_base": opt_bytes_base,
        "opt_bytes_flat": opt_bytes_flat,
        "opt_bytes_reduction": round(reduction, 4),
        "opt_ops_base": len(base_rows),
        "opt_ops_flat": len(flat_rows),
        "opt_concat_gather_scatter_base": len(base_banned),
        "opt_concat_gather_scatter_flat": len(flat_banned),
        "flat_compiles": compiles,
        "flat_recompiles": recompiles,
        "step_time_base_s": round(step_base_s, 6),
        "step_time_flat_s": round(step_flat_s, 6),
        "jsonl": jsonl,
    }
    gates = {
        "bit_identical": bit_identical,
        "no_gather_scatter_concat_in_opt": not flat_banned,
        "one_compile_no_recompiles": compiles == 1 and recompiles == 0,
    }
    result["gates"] = gates
    result["pass"] = all(gates.values())
    monitor.disable()
    print(json.dumps(result))
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
