"""A CPU rehearsal of the whole command at a tiny size: control flow and
the shape of the last line. No number here is a device number."""
import json
import os
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.tests import tiny

SEED = 3_000_000_007        # more than 32 signed bits hold


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 5_000_000_000)
    return module


def test_untraced_run_reports_the_cells_end_to_end_metrics(job):
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny.bert()
    result = run.run_cell(manifest, cell, cfg, traffic,
                          tiny.roomy(limits), SEED, 0.5, False,
                          tiny.CPU, tiny.PEAKS)
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    want = {m["name"] for m in manifest["end_to_end"]
            if cell["name"] in m.get("workloads", [cell["name"]])}
    assert set(result["metrics"]) == want
    units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
    for name, m in result["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    assert result["device"]["memory_peak_bytes"] == 5_000_000_000
    json.dumps(result)


def test_traced_run_reports_the_per_layer_metrics_and_a_breakdown(
        job, monkeypatch):
    from benchmark import reduce_trace
    from jax.profiler import ProfileData
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "synthetic_trace.textproto")) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    # the CPU's trace has no device plane; hand the reduction a TPU-shaped one
    monkeypatch.setattr(
        reduce_trace, "reduce_dir",
        lambda path: reduce_trace.reduce(
            ProfileData.from_serialized_xspace(blob)))
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny.bert()
    result = run.run_cell(manifest, cell, cfg, traffic,
                          tiny.roomy(limits), 11, 0.5, True,
                          tiny.CPU, tiny.PEAKS)
    per_layer = {m["name"] for m in manifest["per_layer"]}
    assert set(result["metrics"]) <= per_layer
    for name in ("host_dispatch_ms", "compiles_in_window", "device_idle_pct",
                 "peak_hbm_gb", "pallas_ms_per_step"):
        assert name in result["metrics"], name
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert 0 < len(result["breakdown"]["device_ops"]) <= 10
    assert 0 < len(result["breakdown"]["idle_gaps"]) <= 10
    assert os.path.isdir(os.path.join(run.WORK_DIR, "trace", cell["name"]))


def test_run_py_off_the_chip_exits_non_zero_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(tiny.ROOT, "benchmark", "run.py"),
         "--workload", "bert_base.pretrain_seq128", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tiny.ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    for line in p.stdout.splitlines():
        assert not line.lstrip().startswith("{"), line


def test_an_unknown_workload_is_refused_before_any_device_is_touched():
    with pytest.raises(SystemExit, match="no workload"):
        run.resolve(tiny.manifest(), "no.such_cell")
