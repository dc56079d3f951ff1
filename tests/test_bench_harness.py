"""bench.py's wiring: it refuses to run without a chip, a stage that
raises ends the run, and --fast selects the headline stages."""
import contextlib
import importlib.util
import io as _io
import json
import os
import sys

import pytest


@pytest.fixture()
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._RESULTS.clear()
    return mod


def test_bench_without_a_chip_exits_nonzero(bench):
    """A measurement that finds no chip fails: no result-shaped line,
    no exit code 0 (the suite runs on the CPU backend)."""
    buf = _io.StringIO()
    with contextlib.redirect_stdout(buf), pytest.raises(SystemExit) as e:
        bench._require_tpu()
    assert e.value.code not in (0, None)
    assert "needs a TPU" in str(e.value.code)
    assert "{" not in buf.getvalue()


def test_main_fast_and_full_stage_selection(bench, monkeypatch):
    """--fast runs only the two headline stages; the full path runs
    pipeline + seq-512 + seq-2048 and banks their metrics."""
    import sys as _sys
    monkeypatch.setattr(bench, "_require_tpu", lambda: None)
    monkeypatch.setattr(bench, "_enable_monitoring_and_cache",
                        lambda: None)
    monkeypatch.setattr(bench, "bench_bert",
                        lambda **k: (111111.0, 2.5))
    monkeypatch.setattr(bench, "bench_resnet",
                        lambda **k: (2500.0, 3.1))
    calls = []
    monkeypatch.setattr(bench, "bench_resnet_pipeline",
                        lambda **k: calls.append("pipe") or (1.0, 2.0))
    monkeypatch.setattr(bench, "bench_bert_seq512",
                        lambda **k: calls.append("s512") or (1.0, 0.0))
    monkeypatch.setattr(bench, "bench_bert_long",
                        lambda **k: calls.append("s2048") or (1.0, 0.0))
    # The subprocess-launching stages (each spawns its own python+jax
    # and runs a full smoke script) are stage-selection no-ops here:
    # their behavior is gated by their own scripts/*_smoke.sh entries in
    # run_full_suite.sh, and running them for real turns this wiring
    # test into a multi-minute integration run.
    monkeypatch.setattr(bench, "bench_serving",
                        lambda **k: (1.0, 2.0, 300.0, 3.0))
    monkeypatch.setattr(bench, "bench_serving_degraded", lambda **k: {
        "serving_degraded_goodput": 1.0,
        "serving_degraded_high_goodput": 1.0})
    monkeypatch.setattr(bench, "bench_collective_overlap", lambda **k: {
        "collective_overlap_ratio": 0.5})
    monkeypatch.setattr(bench, "bench_fused_optimizer", lambda **k: {
        "fused_optimizer_bytes_reduction": 0.5})
    monkeypatch.setattr(bench, "bench_planner", lambda **k: {
        "planner_chosen": "x", "planner_candidates": 1})
    monkeypatch.setattr(bench, "bench_memory_plan", lambda **k: {
        "memory_plan_picked": "none", "memory_plan_ceiling_multiple": 1.0})
    monkeypatch.setattr(bench, "bench_decode", lambda **k: {
        "decode_tokens_per_s": 1.0, "decode_speedup_x": 2.0})
    monkeypatch.setattr(bench, "bench_spec_decode", lambda **k: {
        "decode_spec_speedup_x": 1.5, "decode_accept_rate": 0.95})
    monkeypatch.setattr(bench, "bench_lifecycle", lambda **k: {
        "lifecycle_drain_p99_ms": 1.0, "lifecycle_swap_dropped": 0,
        "lifecycle_soak_goodput": 1.0})
    monkeypatch.setattr(bench, "bench_fleet_telemetry", lambda **k: {
        "fleet_agg_overhead_pct": 0.1, "alert_detection_latency_s": 1.0})
    monkeypatch.setattr(bench, "bench_disagg", lambda **k: {
        "disagg_prefix_hit_rate": 0.5, "disagg_ttft_hit_p50_ms": 1.0,
        "disagg_tokens_per_s": 1.0})
    for argv, expect_extra in ((["bench.py", "--fast"], False),
                               (["bench.py"], True)):
        bench._RESULTS.clear()
        calls.clear()
        monkeypatch.setattr(_sys, "argv", argv)
        import contextlib as _ctx
        import io as _io2
        buf = _io2.StringIO()
        with _ctx.redirect_stdout(buf):
            bench.main()
        out = json.loads(
            [l for l in buf.getvalue().splitlines()
             if l.startswith("{")][-1])
        assert out["value"] == 111111.0
        assert out["resnet50_images_per_sec"] == 2500.0
        assert (len(calls) > 0) == expect_extra
        assert out["provenance"]["device_platform"] == "cpu"
        if expect_extra:
            assert out["bert_seq512_tokens_per_sec"] == 1.0
            assert out["bert_seq2048_tokens_per_sec"] == 1.0
            assert out["disagg_tokens_per_s"] == 1.0


def test_a_stage_that_raises_ends_the_run(bench, monkeypatch):
    """No stage is wrapped in a print-and-carry-on: the exception leaves
    main() and no result line is printed."""
    import sys as _sys
    monkeypatch.setattr(bench, "_require_tpu", lambda: None)
    monkeypatch.setattr(bench, "_enable_monitoring_and_cache",
                        lambda: None)

    def boom(**k):
        raise RuntimeError("kernel did not compile")

    monkeypatch.setattr(bench, "bench_bert", boom)
    monkeypatch.setattr(_sys, "argv", ["bench.py", "--fast"])
    buf = _io.StringIO()
    with contextlib.redirect_stdout(buf), \
            pytest.raises(RuntimeError, match="did not compile"):
        bench.main()
    assert not [l for l in buf.getvalue().splitlines()
                if l.startswith("{")]
