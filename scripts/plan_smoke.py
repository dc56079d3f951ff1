"""Auto-sharding planner smoke gate (tier-1-safe: 8 virtual CPU
devices, seconds).

The PR 11 acceptance run, end to end:

* **bit identity** — ``MegatronConfig(mesh_plan=MEGATRON_RULES)`` must
  reproduce the hand-written dp2/tp2/ep2 megatron layout exactly: every
  PartitionSpec matches in lists form, and training is bit-identical
  (losses AND final params) against the hand config for every step.
* **zero extra recompiles** — an ``hapi.Model.fit(mesh_plan=...)`` run
  compiles exactly as often as the identical plan-free fit (once), with
  ``jit.recompile`` flat.
* **advisor sanity** — ``planner.advise`` returns a non-empty ranked
  table and is rank-stable across calls.
* **prediction vs reality** — an A/B between two mesh factorizations
  (dp8 vs dp2xtp4, same GLOBAL batch fed to both): the layout the cost
  model ranks fastest must BE the measured-fastest. The model sizes are
  chosen so the gap is structural (tp replicates the vocab logits
  matmul per rank), not a timing coin-flip.

Writes the monitor JSONL to --out-dir and prints one JSON result line.
Exit code 0 iff every gate passes.
"""
import argparse
import json
import os
import statistics
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out-dir", default="/tmp/paddle_tpu_plan_smoke")
    ap.add_argument("--steps", type=int, default=3,
                    help="bit-identity training steps")
    ap.add_argument("--timing-steps", type=int, default=5,
                    help="measured steps per A/B layout (post-warmup)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu import hapi, monitor, nn, optimizer as opt
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.parallel import layout, megatron as M, planner

    os.makedirs(args.out_dir, exist_ok=True)
    jsonl = monitor.enable(os.path.join(args.out_dir,
                                        "plan_smoke.jsonl"))
    reg = monitor.registry()
    assert len(jax.devices()) >= 8, "needs 8 virtual devices"

    # -- gate 1+2: one config line == the hand megatron layout --------
    mesh, sizes = M.make_mesh(8, sizes={"dp": 2, "tp": 2, "ep": 2})
    cfg = M.MegatronConfig(vocab_size=128, hidden=32, n_heads=2,
                           layers_per_stage=1, seq_len=16, microbatch=2,
                           n_micro=2)
    params, hand_specs = M.init_params(cfg, mesh)
    mplan = planner.MeshPlan(planner.MEGATRON_RULES, mesh=mesh,
                             name="megatron")
    mismatches = []
    for name, value in params.items():
        nd = np.asarray(jax.device_get(value)).ndim
        want = layout.spec_to_lists(hand_specs[name], nd)
        got = layout.spec_to_lists(mplan.spec_for(name, np.shape(value)),
                                   nd)
        if got != want:
            mismatches.append((name, got, want))

    s_hand, step_hand = M.build_train_step(cfg, mesh)
    s_plan, step_plan = M.build_train_step(
        cfg._replace(mesh_plan=planner.MEGATRON_RULES), mesh)
    rng = np.random.RandomState(0)
    batch_g = cfg.microbatch * sizes["dp"]
    losses_hand, losses_plan = [], []
    for _ in range(args.steps):
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                       (cfg.n_micro, batch_g,
                                        cfg.seq_len)), jnp.int32)
        s_hand, lh = step_hand(s_hand, toks)
        s_plan, lp = step_plan(s_plan, toks)
        losses_hand.append(float(lh))
        losses_plan.append(float(lp))
    params_equal = all(
        np.array_equal(np.asarray(jax.device_get(s_hand["params"][k])),
                       np.asarray(jax.device_get(s_plan["params"][k])))
        for k in s_hand["params"])
    bit_identical = losses_hand == losses_plan and params_equal

    # -- gate 3: fit(mesh_plan=) costs zero extra executables ---------
    def _fit(mesh_plan):
        pt.seed(0)
        r = np.random.RandomState(1)
        x = r.randn(64, 8).astype("f4")
        y = r.randint(0, 3, size=(64,)).astype("i4")
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                            nn.Linear(16, 3))
        m = hapi.Model(net)
        m.prepare(optimizer=opt.Adam(learning_rate=0.05,
                                     parameters=m.parameters()),
                  loss_function=hapi.CrossEntropy())
        c0 = reg.value("jit.compile", 0)
        r0 = reg.value("jit.recompile", 0)
        m.fit(TensorDataset(x, y), batch_size=16, epochs=2, verbose=0,
              mesh_plan=mesh_plan)
        return (reg.value("jit.compile", 0) - c0,
                reg.value("jit.recompile", 0) - r0)

    fit_plan = planner.MeshPlan(planner.TRANSFORMER_RULES,
                                mesh=jax.sharding.Mesh(
                                    np.asarray(jax.devices()).reshape(
                                        4, 2), ("dp", "tp")))
    compiles_hand, rec_hand = _fit(None)
    compiles_plan, rec_plan = _fit(fit_plan)
    zero_extra = (compiles_plan == compiles_hand == 1
                  and rec_plan == rec_hand == 0)

    # -- gate 4: advisor table non-empty + rank-stable ----------------
    acfg = M.MegatronConfig(vocab_size=512, hidden=64, n_heads=4,
                            layers_per_stage=1, seq_len=32, microbatch=8,
                            n_micro=1, use_moe=False)
    t1 = planner.advise(n_devices=8, cfg=acfg, global_batch=8)
    t2 = planner.advise(n_devices=8, cfg=acfg, global_batch=8)
    advisor_ok = (len(t1) >= 2
                  and [r["sizes"] for r in t1] == [r["sizes"] for r in t2]
                  and [r["rank"] for r in t1] == list(range(1,
                                                            len(t1) + 1)))

    # -- gate 5: predicted-fastest == measured-fastest (A/B) ----------
    cand = [{"dp": 8}, {"dp": 2, "tp": 4}]
    ab = planner.advise(cfg=acfg, candidates=cand, global_batch=8)
    predicted_best = ab[0]["sizes"]

    measured = {}
    for c in cand:
        mesh_c, sizes_c = M.make_mesh(8, sizes=c)
        cfg_c = acfg._replace(microbatch=8 // sizes_c["dp"])
        state, step = M.build_train_step(cfg_c, mesh_c)
        r = np.random.RandomState(7)
        toks = jnp.asarray(r.randint(0, acfg.vocab_size,
                                     (acfg.n_micro, 8, acfg.seq_len)),
                           jnp.int32)
        state, loss = step(state, toks)       # warmup: compile
        jax.block_until_ready(loss)
        ts = []
        for _ in range(args.timing_steps):
            t0 = time.perf_counter()
            state, loss = step(state, toks)
            jax.block_until_ready(loss)
            ts.append(time.perf_counter() - t0)
        measured[json.dumps(c, sort_keys=True)] = statistics.median(ts)
    measured_best = json.loads(min(measured, key=measured.get))
    prediction_ok = predicted_best == measured_best

    # -- ledger: record the decision ----------------------------------
    chosen = planner.plan(auto=True, cfg=acfg, n_devices=8,
                          candidates=cand, global_batch=8,
                          name="plan_smoke")
    decision = planner.last_decision()

    result = {
        "metric": "plan_smoke",
        "spec_mismatches": len(mismatches),
        "losses_hand": losses_hand,
        "losses_planned": losses_plan,
        "fit_compiles_hand": compiles_hand,
        "fit_compiles_planned": compiles_plan,
        "fit_recompiles_planned": rec_plan,
        "advisor_table": [{k: r[k] for k in ("rank", "sizes",
                                             "pred_step_s", "bound")}
                          for r in t1],
        "ab_predicted_best": predicted_best,
        "ab_measured_best": measured_best,
        "ab_measured_s": measured,
        "planner_candidates": len(t1),
        "planner_predicted_step_s": round(ab[0]["pred_step_s"], 9),
        "planner_chosen": "x".join(f"{a}{s}" for a, s in
                                   sorted(chosen.sizes.items())
                                   if s > 1),
        "planner_decision_recorded": bool(decision),
        "jsonl": jsonl,
    }
    gates = {
        "specs_match_hand": not mismatches,
        "bit_identical": bit_identical,
        "zero_extra_recompiles": zero_extra,
        "advisor_nonempty_rank_stable": advisor_ok,
        "predicted_matches_measured": prediction_ok,
    }
    result["gates"] = gates
    result["pass"] = all(gates.values())
    monitor.disable()
    print(json.dumps(result))
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
