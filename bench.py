"""Benchmark: BERT-base train tokens/sec/chip + ResNet-50 train images/sec
(SURVEY §6). Runs on the real chip, bf16 compute, donated buffers; prints
ONE JSON line.

Baselines (BASELINE.json "north star": within 10% of Paddle's own V100
numbers): Paddle-era V100 fp32 ResNet-50 ≈ 360 images/s; BERT-base seq128
≈ 25k tokens/s. vs_baseline is ours ÷ that reference.
"""
import json
import time

import numpy as np

BERT_BASELINE_TOKENS_S = 25000.0   # Paddle V100 BERT-base seq128 approx
RESNET_BASELINE_IMG_S = 360.0      # Paddle V100 fp32 ResNet-50 approx


def _normalize_u8(xb):
    """uint8 image batch -> normalized f32 on device (shared by both
    ResNet benches so they measure identical work)."""
    return (xb.astype("float32") / 255.0 - 0.45) / 0.22


def bench_bert(batch=64, seq=128, steps=32, inner=8, measured_key=None,
               **cfg_kw):
    """`inner` REAL optimizer steps (distinct resident batches) run per
    compiled call — one dispatch covers `inner` steps, so the
    host-dispatch round-trip amortizes instead of flooring the step
    time. tok/s counts batch*seq*inner per call."""
    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer as opt, jit, amp
    from paddle_tpu.models.bert import BertConfig, BertForPretraining

    pt.seed(0)
    cfg = BertConfig.base(**cfg_kw)
    model = BertForPretraining(cfg)
    o = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())

    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size,
                      (inner, batch, seq)).astype("i4")
    mlm = np.where(rng.rand(inner, batch, seq) < 0.15,
                   rng.randint(0, cfg.vocab_size, (inner, batch, seq)), -1
                   ).astype("i4")
    nsp = rng.randint(0, 2, (inner, batch)).astype("i4")

    def one(ids, mlm, nsp):
        with amp.auto_cast(dtype="bfloat16"):
            logits, nsp_logits = model(ids)
        loss = model.loss(logits.astype("float32"),
                          nsp_logits.astype("float32"), mlm, nsp)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    def step(ids_k, mlm_k, nsp_k):
        loss = None
        for i in range(inner):
            loss = one(ids_k[i], mlm_k[i], nsp_k[i])
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o])
    t_ids, t_mlm, t_nsp = pt.to_tensor(ids), pt.to_tensor(mlm), \
        pt.to_tensor(nsp)
    fn(t_ids, t_mlm, t_nsp)  # compile
    loss = fn(t_ids, t_mlm, t_nsp)
    loss.numpy()  # sync
    n_calls = max(1, steps // inner)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        loss = fn(t_ids, t_mlm, t_nsp)
    loss.numpy()
    dt = (time.perf_counter() - t0) / (n_calls * inner)
    if measured_key:
        m = _measured_mfu(dt, per_call_steps=inner)
        if m is not None:
            _RESULTS[measured_key] = m
    return batch * seq / dt, float(loss.numpy())


# Headline ResNet layout. scripts/bench_nhwc_resnet.py measures
# NCHW vs NHWC vs NHWC+pallas-BN on chip; flip this (and the pallas
# batch_norm auto default) to whatever wins there.
RESNET_FORMAT = "NCHW"


def bench_resnet(batch=128, steps=12, inner=4, data_format=None,
                 measured_key=None):
    """`inner` real steps per compiled call (distinct resident uint8
    batches, normalized on device) — see bench_bert."""
    import paddle_tpu as pt
    from paddle_tpu import nn, optimizer as opt, jit, amp
    from paddle_tpu.models.resnet import resnet50

    data_format = data_format or RESNET_FORMAT
    pt.seed(0)
    model = resnet50(data_format=data_format)
    o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                     parameters=model.parameters())
    rng = np.random.RandomState(0)
    shape = (inner, batch, 3, 224, 224) if data_format == "NCHW" \
        else (inner, batch, 224, 224, 3)
    x = (rng.rand(*shape) * 255).astype("u1")
    y = rng.randint(0, 1000, (inner, batch)).astype("i4")

    def one(xb, yb):
        with amp.auto_cast(dtype="bfloat16"):
            logits = model(_normalize_u8(xb))
        loss = pt.nn.functional.cross_entropy(logits.astype("float32"), yb)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    def step(x_k, y_k):
        loss = None
        for i in range(inner):
            loss = one(x_k[i], y_k[i])
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o])
    tx, ty = pt.to_tensor(x), pt.to_tensor(y)
    fn(tx, ty)  # compile
    loss = fn(tx, ty)
    loss.numpy()
    n_calls = max(1, steps // inner)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        loss = fn(tx, ty)
    loss.numpy()
    dt = (time.perf_counter() - t0) / (n_calls * inner)
    if measured_key:
        m = _measured_mfu(dt, per_call_steps=inner)
        if m is not None:
            _RESULTS[measured_key] = m
    return batch / dt, float(loss.numpy())


def bench_resnet_pipeline(batch=128, steps=8):
    """ResNet fed through the REAL input pipeline (io.DataLoader over the
    C++ native batcher, csrc/core.cpp) instead of one resident batch —
    the perf evidence for the host-side arena/prefetch path.

    Feeds uint8 images (like a real decoded-JPEG pipeline) and normalizes
    on device inside the jitted step, so host→device moves 1/4 the bytes.
    Also reports the loader-only rate (C++ shuffle+gather+prefetch), which
    is the csrc claim proper — end-to-end additionally rides the
    host-to-device link."""
    import paddle_tpu as pt
    from paddle_tpu import optimizer as opt, jit, amp, io
    from paddle_tpu.models.resnet import resnet50

    pt.seed(0)
    model = resnet50()
    o = opt.Momentum(learning_rate=0.1, momentum=0.9,
                     parameters=model.parameters())
    rng = np.random.RandomState(0)
    n = batch * (steps + 2)
    x = (rng.rand(n, 3, 224, 224) * 255).astype("u1")
    y = rng.randint(0, 1000, (n,)).astype("i4")
    ds = io.TensorDataset(x, y)
    loader = io.DataLoader(ds, batch_size=batch, shuffle=True,
                           drop_last=True, use_native=True)

    # loader-only rate: C++ background shuffle+assemble, no device in loop
    for _ in loader:
        pass  # warm epoch (thread spin-up)
    t0 = time.perf_counter()
    got = 0
    for xb, _ in loader:
        got += xb.shape[0]
    loader_ips = got / (time.perf_counter() - t0)

    def step(xb, yb):
        with amp.auto_cast(dtype="bfloat16"):
            logits = model(_normalize_u8(xb))
        loss = pt.nn.functional.cross_entropy(logits.astype("float32"), yb)
        loss.backward()
        o.step()
        o.clear_grad()
        return loss

    fn = jit.to_static(step, models=[model], optimizers=[o])
    it = iter(loader)
    xb, yb = next(it)
    fn(pt.to_tensor(xb), pt.to_tensor(yb))  # compile
    done = 0
    t0 = time.perf_counter()
    loss = None
    for xb, yb in it:
        loss = fn(pt.to_tensor(xb), pt.to_tensor(yb))
        done += xb.shape[0]
        if done >= batch * steps:
            break
    loss.numpy()
    dt = time.perf_counter() - t0
    return done / dt, loader_ips


def bench_bert_long(batch=4, seq=2048, steps=8):
    """Long-context secondary metric: BERT-base-width encoder at seq 2048
    — the regime where the flash kernel's O(S) memory vs sdpa's O(S^2)
    scores matters on HBM. inner=2 keeps the unrolled 12-layer seq-2048
    graph's compile time bounded."""
    return bench_bert(batch=batch, seq=seq, steps=steps, inner=2,
                      measured_key="bert_seq2048_mfu_measured",
                      max_position_embeddings=2048)


def bench_bert_seq512(batch=16, seq=512, steps=16, inner=4):
    """Long-sequence headline (VERDICT r4 task 4): seq 512 is the
    smallest shape the flash gate routes to the Pallas kernel, and
    batch 16 x seq 512 keeps tokens/step identical to the seq-128
    headline (8,192) so tok/s is directly comparable."""
    return bench_bert(batch=batch, seq=seq, steps=steps, inner=inner,
                      measured_key="bert_seq512_mfu_measured")


def bench_serving(requests=400, clients=8, max_batch=32,
                  timeout_ms=2.0, dim=256):
    """Online-serving stage: the latency/QPS face of the ledger, next
    to training MFU. A warmed ServingEngine over a (dim -> 4*dim ->
    dim) MLP absorbs ragged concurrent requests (sizes 1/3/7/13) from
    `clients` threads; dynamic batching coalesces them into bucket
    shapes, so the numbers measure the serving tier itself, not a
    compile storm. Returns (p50_ms, p99_ms, qps, mean_batch_fill)."""
    import threading
    import paddle_tpu as pt
    from paddle_tpu import inference, monitor, nn, serving

    pt.seed(0)
    model = nn.Sequential(nn.Linear(dim, 4 * dim), nn.ReLU(),
                          nn.Linear(4 * dim, dim))
    eng = serving.ServingEngine(
        inference.Predictor(model), buckets=[8, max_batch],
        max_batch=max_batch, timeout_ms=timeout_ms, queue_depth=2048)
    eng.warmup([((dim,), "float32")])

    sizes = [1, 3, 7, 13]
    per_client = requests // clients
    latencies = []
    lock = threading.Lock()
    barrier = threading.Barrier(clients)

    def client(k):
        rng = np.random.RandomState(k)
        barrier.wait()
        for i in range(per_client):
            x = rng.rand(sizes[(k + i) % len(sizes)], dim).astype("f4")
            t0 = time.perf_counter()
            eng.run(x, timeout=60)
            with lock:
                latencies.append((time.perf_counter() - t0) * 1e3)

    threads = [threading.Thread(target=client, args=(k,))
               for k in range(clients)]
    t_start = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t_start
    eng.close()

    fill = monitor.registry().value("serving.batch_fill") or {}
    mean_fill = (fill.get("sum", 0.0) / fill["count"]) \
        if isinstance(fill, dict) and fill.get("count") else 0.0
    lat = sorted(latencies)

    def pct(p):
        return lat[min(int(len(lat) * p), len(lat) - 1)] if lat else 0.0

    return pct(0.50), pct(0.99), len(lat) / wall, mean_fill


def bench_collective_overlap(timeout_s=600):
    """Gradient-communication stage: runs scripts/comm_smoke.py in a
    subprocess pinned to 8 virtual CPU devices (the collective ring
    needs a multi-device mesh regardless of what backend the rest of
    the bench runs on) and banks its measurements — exposed wire
    seconds exact vs overlap, bucket count, wire/logical comm bytes,
    quantized loss parity. The sentinel bands these via
    collective_overlap_* keys."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "comm_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir", "/tmp/paddle_tpu_bench_comm"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"comm_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    return {
        "collective_overlap_exposed_wire_s":
            r["exposed_wire_overlap_s"],
        "collective_overlap_exact_wire_s": r["exposed_wire_exact_s"],
        "collective_overlap_ratio": r["overlap_ratio"],
        "collective_overlap_bucket_count": r["bucket_count"],
        "comm_bytes_logical": r["comm_bytes_logical"],
        "comm_bytes_wire_int8": r["comm_bytes_wire_int8"],
        "comm_wire_reduction_int8_x": r["wire_reduction_int8_x"],
        "comm_wire_reduction_int4_x": r["wire_reduction_int4_x"],
        "comm_quantized_loss_rel_err": r["quantized_loss_rel_err"],
    }


def bench_serving_degraded(timeout_s=600):
    """Degraded-serving stage: runs scripts/serving_chaos_smoke.py in a
    subprocess pinned to 4 virtual CPU devices and banks what the fleet
    keeps while broken — goodput with 1 of 4 replicas hung mid-load,
    high-priority goodput under 2x overload, and the hedge overhead
    (hedged fraction of traffic) paid for the straggler rescue. The
    sentinel bands the goodputs as floors and the hedge fraction as a
    ceiling — resilience regressions show up here before they show up
    in an outage."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "serving_chaos_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_serving_chaos"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"serving_chaos_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    hedge = r["hedge_win"]
    return {
        "serving_degraded_goodput": r["hang_failover"]["goodput"],
        "serving_degraded_high_goodput":
            r["overload_shed"]["high_goodput"],
        "serving_degraded_hedge_frac":
            round(hedge["hedged"] / max(hedge["submitted"], 1), 4),
        "serving_degraded_failovers": r["hang_failover"]["failovers"],
        "serving_degraded_shed": r["overload_shed"]["total_shed"],
    }


def bench_fused_optimizer(timeout_s=600):
    """Fused-optimizer stage: runs scripts/arena_smoke.py in a
    subprocess (CPU-pinned — the arena layout and the opt.* byte ledger
    are backend-independent) and banks its measurements: optimizer-scope
    bytes_accessed per 5-step run under the multi-tensor per-leaf
    baseline vs the flat arena, the reduction fraction, the surviving
    concat/gather/scatter count, and the post-compile step wall time.
    The sentinel bands the byte metrics tight (deterministic functions
    of the model layout + packing) and the wall time very wide."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "arena_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_arena"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"arena_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    return {
        "fused_optimizer_opt_bytes_base": r["opt_bytes_base"],
        "fused_optimizer_opt_bytes_flat": r["opt_bytes_flat"],
        "fused_optimizer_bytes_reduction": r["opt_bytes_reduction"],
        "fused_optimizer_banned_ops_flat":
            r["opt_concat_gather_scatter_flat"],
        "fused_optimizer_step_time_s": r["step_time_flat_s"],
    }


def bench_planner(timeout_s=600):
    """Auto-sharding planner stage: runs scripts/plan_smoke.py in a
    subprocess pinned to 8 virtual CPU devices and banks the advisor's
    decision: candidate count (tight band — drift means the
    factorization enumeration changed), the winning layout's predicted
    step seconds (very wide band — a modeled time), and the chosen
    factorization label. The smoke itself enforces the hard gates
    (bit-identity with the hand megatron layout, zero extra
    recompiles, predicted-fastest == measured-fastest)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "plan_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_plan"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"plan_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    return {
        "planner_candidates": r["planner_candidates"],
        "planner_predicted_step_s": r["planner_predicted_step_s"],
        "planner_chosen": r["planner_chosen"],
        "planner_gates_pass": bool(r["pass"]),
    }


def bench_memory_plan(timeout_s=600):
    """Planned-memory stage: runs scripts/remat_smoke.py in a
    subprocess and banks the memory-policy loop's decision: how many
    times past the no-remat ceiling the picked policy trains (tight
    band — the headline capability must not shrink), the picked rung,
    predicted vs simulated peak under the policy, the offload worker's
    exposed-wait fraction, and warm step seconds under none/remat
    (very wide bands — CPU wall-clock noise). The smoke itself
    enforces the hard gates (pre-flight peak under the limit, picker
    never infeasible or host-over-budget, bit-identity)."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8"
                        ).strip()
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "remat_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_memory_plan"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"remat_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    return {
        "memory_plan_ceiling_multiple": r["ceiling_multiple"],
        "memory_plan_picked": r["picked"],
        "memory_plan_predicted_peak_bytes": r["predicted_peak_bytes"],
        "memory_plan_measured_peak_bytes":
            r["measured_peak_under_policy"],
        "memory_plan_offload_exposed_frac": r["offload_exposed_frac"],
        "memory_plan_offload_transfer_s":
            round(r["offload_transfer_s"], 6),
        "memory_plan_step_s_none": round(r["step_s_none"], 6),
        "memory_plan_step_s_remat": round(r["step_s_remat"], 6),
        "memory_plan_gates_pass": bool(r["pass"]),
    }


def bench_decode(timeout_s=600):
    """Generative-decode stage: runs scripts/decode_smoke.py in a
    subprocess (CPU, 2 virtual devices for the scale-up phase) and
    banks the continuous-batching numbers: sustained tokens/s under
    continuous refill, the speedup over the drain run-to-completion
    baseline at the same slot count, decode-batch occupancy, and the
    prefill p50 / decode p99 step latencies. The sentinel bands the
    wall-clock rates very wide (shared-box noise), the speedup and
    occupancy tight — those are scheduling ratios, not clock
    measurements, and a drop means the refill discipline regressed."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "decode_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_decode"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"decode_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    tp = r["throughput"]
    return {
        "decode_tokens_per_s": tp["continuous_tokens_per_s"],
        "decode_drain_tokens_per_s": tp["drain_tokens_per_s"],
        "decode_speedup_x": tp["speedup_x"],
        "decode_batch_occupancy": tp["continuous_occupancy"],
        "decode_prefill_p50_ms": tp["prefill_p50_ms"],
        "decode_p99_ms": tp["decode_p99_ms"],
        "decode_ttft_p50_ms": tp.get("ttft_p50_ms"),
        "decode_ttft_p99_ms": tp.get("ttft_p99_ms"),
        "decode_tpot_p50_ms": tp.get("tpot_p50_ms"),
        "decode_tpot_p99_ms": tp.get("tpot_p99_ms"),
        "decode_gates_pass": bool(r["ok"]),
    }


def bench_spec_decode(timeout_s=900):
    """Speculative-decode stage: runs scripts/spec_smoke.py in a
    subprocess (CPU) and banks the draft-verify numbers: plain sampled
    tokens/s vs speculative at k=4 and k=8 on the distilled demo pair,
    the two speedup ratios, and the measured accept rates. The
    sentinel bands the wall-clock rates very wide; the speedup ratios
    get a wide band too (they divide two CPU clocks), but the accept
    rate is pure arithmetic over the verify ledger — tight band, a
    drop means the accept-prefix rule or the draft distillation
    regressed, not the weather."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    smoke = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "scripts", "spec_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_spec"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"spec_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    sp = r["speedup"]
    return {
        "decode_sampled_tokens_per_s": sp["plain_tokens_per_s"],
        "decode_spec_tokens_per_s": sp["spec_k8_tokens_per_s"],
        "decode_spec_speedup_x": sp["speedup_k4_x"],
        "decode_spec_speedup_k8_x": sp["speedup_k8_x"],
        "decode_accept_rate": sp["accept_rate_k4"],
        "decode_accept_rate_k8": sp["accept_rate_k8"],
        "decode_spec_gates_pass": bool(r["ok"]),
    }


def bench_lifecycle(timeout_s=900):
    """Serving-lifecycle stage: runs scripts/lifecycle_smoke.py and a
    short scripts/soak_chaos.py in subprocesses (CPU, 4 virtual
    devices) and banks the zero-downtime numbers: the p99 of a full
    fleet drain (in-flight decode streams run to completion), requests
    dropped across a rolling weight hot-swap (must be zero — the swap
    migrates, never sheds), and the goodput the fleet holds through the
    mixed-fault chaos soak. The sentinel bands the drain latency very
    wide (it's CPU decode wall-clock), but swap drops and soak goodput
    tight — those are correctness ratios, and any drift means the
    drain/migrate/swap discipline regressed."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    here = os.path.dirname(os.path.abspath(__file__))
    smoke = os.path.join(here, "scripts", "lifecycle_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_lifecycle"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"lifecycle_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    soak = os.path.join(here, "scripts", "soak_chaos.py")
    sproc = subprocess.run(
        [sys.executable, soak, "--out-dir",
         "/tmp/paddle_tpu_bench_soak", "--duration", "20"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    sline = next((ln for ln in reversed(sproc.stdout.splitlines())
                  if ln.startswith("{")), None)
    if sproc.returncode != 0 or sline is None:
        raise RuntimeError(
            f"soak_chaos rc={sproc.returncode}: "
            f"{(sproc.stderr or sproc.stdout)[-400:]}")
    s = json.loads(sline)
    return {
        "lifecycle_drain_p99_ms": r["drain_p99_ms"],
        "lifecycle_swap_dropped": r["swap_dropped"],
        "lifecycle_soak_goodput": s["goodput"],
        "lifecycle_soak_requests": s["requests"],
        "lifecycle_gates_pass": bool(r["ok"]),
        "lifecycle_soak_gates_pass": bool(s["ok_gate"]),
    }


def bench_fleet_telemetry(timeout_s=600):
    """Fleet telemetry stage: runs scripts/telemetry_smoke.py (a
    4-process decode fleet publishing snapshots, with one straggler and
    one compile-storm worker injected) and banks the plane's two costs:
    the CPU a worker burns publishing snapshots as a percentage of its
    run (must stay tiny — this is the price every fleet member pays)
    and the wall-clock from load start to the first anomaly alert
    firing (the page-the-operator latency). Both band wide in the
    sentinel — they are wall-clock on a shared box — but the gates_pass
    bit is exact: merge oracle, alert discipline, goodput
    reconciliation, and disabled-mode silence all held."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    here = os.path.dirname(os.path.abspath(__file__))
    smoke = os.path.join(here, "scripts", "telemetry_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_telemetry", "--fast"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"telemetry_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    return {
        "fleet_agg_overhead_pct": r["fleet_agg_overhead_pct"],
        "alert_detection_latency_s": r["alert_detection_latency_s"],
        "fleet_sources": r["sources"],
        "telemetry_gates_pass": bool(r["ok"]),
    }


def bench_disagg(timeout_s=900):
    """Disaggregated-serving stage: runs scripts/disagg_smoke.py (a
    prefill pool and a decode pool split across 2 virtual CPU devices,
    KV handed off over the PR 12 comm model, with a shared-prefix
    cache in front of prefill) and banks the split's headline numbers:
    the prefix-cache hit rate at 50% structured reuse, the hit-vs-miss
    TTFT split the cache buys (a hit skips prefill entirely, so hit
    p50 must stay well under miss p50), the per-request KV handoff
    cost, and the split topology's end-to-end tokens/s. Wall-clock
    series band wide in the sentinel (shared box); the gates_pass bit
    is exact: bit-parity with the single-engine oracle through a
    mid-stream drain, handoff bytes == plan, per-pool SLO autoscale,
    and goodput >= 0.90 with one prefill replica hung."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    here = os.path.dirname(os.path.abspath(__file__))
    smoke = os.path.join(here, "scripts", "disagg_smoke.py")
    proc = subprocess.run(
        [sys.executable, smoke, "--out-dir",
         "/tmp/paddle_tpu_bench_disagg"],
        capture_output=True, text=True, timeout=timeout_s, env=env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        raise RuntimeError(
            f"disagg_smoke rc={proc.returncode}: "
            f"{(proc.stderr or proc.stdout)[-400:]}")
    r = json.loads(line)
    return {
        "disagg_prefix_hit_rate": r["prefix_hit_rate"],
        "disagg_ttft_hit_p50_ms": r["ttft_hit_p50_ms"],
        "disagg_ttft_miss_p50_ms": r["ttft_miss_p50_ms"],
        "disagg_handoff_ms": r["handoff_p50_ms"],
        "disagg_tokens_per_s": r["tokens_per_s"],
        "disagg_gates_pass": bool(r["ok"]),
    }


def bench_hotspot(label=None, top_k=5):
    """Hotspot stage: parse the newest captured step executable's HLO
    into the per-op cost ledger (monitor.profile) and bank the ranked
    fusion menu next to the throughput it explains — which region, at
    what attributed fraction, with how much memory-bound headroom. The
    sentinel bands hotspot_count tight (the menu must not silently go
    empty) and the fractions wide."""
    from paddle_tpu import monitor
    rep = monitor.profile.report(label=label, top_k=top_k,
                                 emit_records=False)
    if rep is None:
        return None
    recon = rep.get("flops_reconciliation")
    top = rep["hotspots"][0] if rep["hotspots"] else None
    return {
        "hotspot_count": len(rep["hotspots"]),
        "hotspot_attributed_frac": round(rep["attributed_frac"], 4),
        "hotspot_top_headroom_s":
            round(top["headroom_s"], 9) if top else None,
        "hotspot_flops_reconciliation":
            round(recon, 4) if recon else None,
        "hotspot_top_regions": [
            {"region": h["region"], "bound": h["bound"],
             "flops": h["flops"],
             "headroom_s": round(h["headroom_s"], 9)}
            for h in rep["hotspots"][:3]],
        "hotspot_device_kind": rep["ceilings"]["device_kind"],
        "hotspot_assumed_roofline": rep["ceilings"]["assumed"],
    }


def bench_memory(label=None, top_k=5):
    """Memory stage: run the buffer-liveness model (monitor.memory)
    over the newest captured step executable and bank the predicted
    HBM peak next to XLA's own memory_analysis() peak and the live
    device watermark — which class (param / activation / opt_state /
    temp) owns the peak, at what attributed fraction. The sentinel
    bands the reconciliation tight (the model must keep agreeing with
    the compiler) and the absolute peaks wide (they move with every
    legitimate model-size change)."""
    from paddle_tpu import monitor
    rep = monitor.memory.report(label=label, top_k=top_k,
                                emit_records=False)
    if rep is None:
        return None
    recon = rep.get("reconciliation")
    top = rep["contributors"][0] if rep["contributors"] else None
    return {
        "memory_predicted_peak_bytes": rep["predicted_peak_bytes"],
        "memory_xla_peak_bytes": rep["xla_peak_bytes"],
        "memory_reconciliation": round(recon, 4) if recon else None,
        "memory_attributed_frac": round(rep["attributed_frac"], 4),
        "memory_measured_peak_bytes": rep["measured_peak_bytes"],
        "memory_by_class": rep["by_class"],
        "memory_top_contributor": (
            {"class": top["class"], "region": top["region"],
             "bytes": top["bytes"]} if top else None),
        "memory_n_donated": rep["n_donated"],
    }


_RESULTS = {}  # metrics banked as each stage finishes (partial-credit)


def _mfu(rate_per_s, flops_per_item):
    """MFU from a throughput: items/s × train flops/item ÷ the live
    device's peak bf16 flops (monitor's per-device_kind table, or the
    PADDLE_TPU_FLOPS_CEILING override). None when the ceiling is
    unknown (CPU, unrecognized kind) — absent beats fabricated."""
    from paddle_tpu import monitor
    peak = monitor.peak_flops_for_device()
    if not peak or not rate_per_s:
        return None
    return round(rate_per_s * flops_per_item / peak, 4)


def _measured_mfu(step_time_s, label="jit.step", per_call_steps=1):
    """MFU from the XLA-counted flops of the bench's compiled step
    (monitor.xla captures the executable on first compile): flops per
    call ÷ steps-per-call, over the measured step time × peak.
    Complements _mfu's analytic 6N figure — agreement within ~20%
    validates the analytic denominator; a bigger gap means remat, a
    miscounted model, or a fused step doing extra work. None off-TPU
    or when no capture landed (absent beats fabricated)."""
    from paddle_tpu import monitor
    f = monitor.xla.flops(label)
    peak = monitor.peak_flops_for_device()
    if not f or not peak or not step_time_s:
        return None
    return round(f / per_call_steps / step_time_s / peak, 4)


def _note_mfu_divergence(prefix):
    """Bank an explicit flag when analytic and XLA-measured MFU disagree
    by >20% — the ratio rides the perf line so a drifting denominator
    is visible in the ledger, not just in a warning on stderr."""
    a = _RESULTS.get(f"{prefix}_mfu")
    m = _RESULTS.get(f"{prefix}_mfu_measured")
    if a and m and abs(m / a - 1.0) > 0.2:
        _RESULTS[f"{prefix}_mfu_divergence"] = round(m / a, 3)


def _bert_flops_per_token():
    """Params-only 6N convention (no attention quadratic term), the
    common MFU denominator — keeps seq-128/512/2048 rows comparable."""
    from paddle_tpu import monitor
    return monitor.transformer_train_flops_per_token(
        monitor.BERT_BASE_PARAMS)


def _provenance():
    """Who/where/what for every emitted line: time, host, git revision
    where there is a git, and the device JAX reports."""
    import datetime
    import os
    import platform
    import subprocess
    import jax
    from paddle_tpu import monitor
    here = os.path.dirname(os.path.abspath(__file__))
    rev = None
    if os.path.isdir(os.path.join(here, ".git")):
        rev = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10, cwd=here).stdout.strip() or None
    d = jax.devices()[0]
    return {
        "measured_at": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "host": platform.node(),
        "git_rev": rev,
        "jax_version": jax.__version__,
        "device_platform": d.platform,
        "device_kind": d.device_kind,
        "device_count": len(jax.devices()),
        "peak_flops_bf16": monitor.peak_flops_for_device(d),
    }


def _append_result_jsonl(out):
    """Append the result line to $PADDLE_TPU_BENCH_JSONL (one JSON
    object per line) — the running artifact scripts/perf_sentinel.py
    audits for regressions."""
    import os
    path = os.environ.get("PADDLE_TPU_BENCH_JSONL", "")
    if not path:
        return
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "a") as fh:
        fh.write(json.dumps(out) + "\n")


def _enable_monitoring_and_cache():
    """Persist XLA compilations across bench processes (where
    device.enable_compilation_cache says) and turn on the in-memory
    monitor so compiles_per_stage can ride the perf line."""
    from paddle_tpu import monitor
    from paddle_tpu.device import enable_compilation_cache
    print(f"compile cache: {enable_compilation_cache()}", flush=True)
    monitor.enable()  # no sink path: in-memory counters only
    # label every layer/optimizer scope in the step HLO so the hotspot
    # stage can attribute the cost ledger to real model parts
    monitor.profile.enable()


_COMPILES_SEEN = {"n": 0}


def _record_stage_compiles(stage):
    """Bank how many fresh XLA executables this stage minted (jit +
    executor compile counters) — next to throughput, the evidence that
    shape bucketing / the persistent cache keep the compile count flat."""
    from paddle_tpu import monitor
    reg = monitor.registry()
    total = int(reg.value("jit.compile", 0)) + \
        int(reg.value("executor.compile", 0)) + \
        int(reg.value("inference.compile", 0)) + \
        int(reg.value("inference.aot_warmup", 0))
    delta, _COMPILES_SEEN["n"] = total - _COMPILES_SEEN["n"], total
    _RESULTS.setdefault("compiles_per_stage", {})[stage] = delta


def _require_tpu():
    """A measurement that finds no chip fails: no CPU fallback, no
    result-shaped line."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench.py needs a TPU; JAX found {d.platform!r} "
                         f"({d.device_kind!r})")


def main():
    """Every stage runs or the run fails: a stage that raises ends the
    process with its traceback and a non-zero exit code."""
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="headline BERT + ResNet only (skips "
                         "pipeline/long-seq stages)")
    args = ap.parse_args()
    _require_tpu()
    _RESULTS["provenance"] = _provenance()
    _enable_monitoring_and_cache()
    bert_tps, bert_loss = bench_bert(measured_key="bert_mfu_measured")
    _record_stage_compiles("bert_seq128")
    # partial lines are deliberately NOT json (exactly one JSON line at
    # the end) — they leave evidence if the harness kills us mid-run
    print(f"partial bert_tokens_per_sec={bert_tps:.1f}", flush=True)
    _RESULTS.update(value=round(bert_tps, 1),
                    vs_baseline=round(bert_tps / BERT_BASELINE_TOKENS_S,
                                      3),
                    bert_loss=round(bert_loss, 4),
                    bert_mfu=_mfu(bert_tps, _bert_flops_per_token()))
    _note_mfu_divergence("bert")
    hs = bench_hotspot()  # newest capture: the BERT train step
    if hs:
        print(f"partial hotspot_count={hs['hotspot_count']} "
              f"attributed={hs['hotspot_attributed_frac']}", flush=True)
        _RESULTS.update(hs)
    mm = bench_memory()  # same capture the hotspot stage read
    if mm:
        print(f"partial memory_reconciliation="
              f"{mm['memory_reconciliation']} "
              f"attributed={mm['memory_attributed_frac']}", flush=True)
        _RESULTS.update(mm)
    rn_ips, rn_loss = bench_resnet(measured_key="resnet50_mfu_measured")
    _record_stage_compiles("resnet50")
    print(f"partial resnet_images_per_sec={rn_ips:.1f}", flush=True)
    from paddle_tpu import monitor as _mon
    _RESULTS.update(
        resnet50_images_per_sec=round(rn_ips, 1),
        resnet50_vs_baseline=round(rn_ips / RESNET_BASELINE_IMG_S, 3),
        resnet50_loss=round(rn_loss, 4),
        resnet50_mfu=_mfu(rn_ips, _mon.RESNET50_TRAIN_FLOPS_PER_IMAGE))
    _note_mfu_divergence("resnet50")
    s50, s99, sqps, sfill = bench_serving()
    print(f"partial serving_qps={sqps:.1f} p99_ms={s99:.2f}", flush=True)
    _RESULTS.update(serving_p50_ms=round(s50, 3),
                    serving_p99_ms=round(s99, 3),
                    serving_qps=round(sqps, 1),
                    serving_batch_fill=round(sfill, 2))
    _record_stage_compiles("serving")
    sd = bench_serving_degraded()
    print(f"partial serving_degraded_goodput="
          f"{sd['serving_degraded_goodput']} "
          f"high={sd['serving_degraded_high_goodput']}", flush=True)
    _RESULTS.update(sd)
    if not args.fast:
        pipe_ips, loader_ips = bench_resnet_pipeline()
        _record_stage_compiles("resnet50_pipeline")
        print(f"partial pipeline_images_per_sec={pipe_ips:.1f}",
              flush=True)
        _RESULTS.update(
            resnet50_pipeline_images_per_sec=round(pipe_ips, 1),
            loader_images_per_sec=round(loader_ips, 1))
        for key, fn in (("bert_seq512_tokens_per_sec", bench_bert_seq512),
                        ("bert_seq2048_tokens_per_sec", bench_bert_long)):
            tps, _ = fn()
            _record_stage_compiles(key.replace("_tokens_per_sec", ""))
            print(f"partial {key}={tps:.1f}", flush=True)
            _RESULTS[key] = round(tps, 1)
            _RESULTS[key.replace("_tokens_per_sec", "_mfu")] = \
                _mfu(tps, _bert_flops_per_token())
            _note_mfu_divergence(key.replace("_tokens_per_sec", ""))
        # the CPU-subprocess stages: each banks its dict and prints the
        # keys named here on one partial line
        for stage, keys in (
                (bench_collective_overlap, ("collective_overlap_ratio",)),
                (bench_fused_optimizer,
                 ("fused_optimizer_bytes_reduction",)),
                (bench_planner, ("planner_chosen", "planner_candidates")),
                (bench_memory_plan, ("memory_plan_picked",
                                     "memory_plan_ceiling_multiple")),
                (bench_decode, ("decode_tokens_per_s",
                                "decode_speedup_x")),
                (bench_spec_decode, ("decode_spec_speedup_x",
                                     "decode_accept_rate")),
                (bench_lifecycle, ("lifecycle_drain_p99_ms",
                                   "lifecycle_soak_goodput")),
                (bench_fleet_telemetry, ("fleet_agg_overhead_pct",
                                         "alert_detection_latency_s")),
                (bench_disagg, ("disagg_prefix_hit_rate",
                                "disagg_ttft_hit_p50_ms",
                                "disagg_tokens_per_s"))):
            out = stage()
            print("partial " + " ".join(f"{k}={out[k]}" for k in keys),
                  flush=True)
            _RESULTS.update(out)
    # ONE output schema: everything was banked into _RESULTS as its
    # stage finished
    result = {"metric": "bert_base_tokens/sec/chip", "unit": "tokens/s",
              **_RESULTS}
    _append_result_jsonl(result)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
