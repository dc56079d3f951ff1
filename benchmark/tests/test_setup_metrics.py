"""The eight readers that move `setup_s`, on a CPU rehearsal of one cell at
a tiny size: each finds a number in the program's records, and the cut at
the window's first call keeps the reference's programs out. No number here
is a device number."""
import jax
import pytest

from benchmark import run
from benchmark.tests import tiny

READERS = ("first_call_trace_s", "first_call_lower_s", "first_call_backend_s",
           "step_loaded_from_cache", "step_pallas_instances",
           "setup_programs_compiled", "setup_backend_s",
           "import_paddle_tpu_s")
CACHE_SETTINGS = ("jax_compilation_cache_dir",
                  "jax_persistent_cache_min_compile_time_secs",
                  "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture()
def fresh_records(tmp_path, monkeypatch):
    """A process's records as a run of the benchmark finds them: no step
    called yet, no executable captured, a compile cache that keeps every
    program; the cache's settings are put back afterwards."""
    from jax.experimental.compilation_cache import compilation_cache
    from paddle_tpu import monitor
    job = run.load_module("jobs", "train_loop")
    # the CPU backend reports no memory statistics
    monkeypatch.setattr(job, "device_peak_bytes", lambda: 5_000_000_000)
    for name in ("jit.compile", "jit.cache_hit"):
        monitor.registry().remove(name)
    monitor.xla.reset()
    before = {k: getattr(jax.config, k) for k in CACHE_SETTINGS}
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    yield job
    for k, v in before.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()
    monitor.disable()


def test_the_setup_readers_each_find_a_number_and_cut_the_reference_out(
        fresh_records):
    from paddle_tpu import monitor
    job = fresh_records
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny.bert()
    result = run.run_cell(manifest, cell, cfg, traffic, tiny.roomy(limits),
                          3_000_000_011, 0.3, False, tiny.CPU, tiny.PEAKS)
    assert result["correct"] is True
    context = {"cell": cell, "config": cfg, "traffic": traffic}
    entries = {m["name"]: m for m in manifest["per_layer"]}
    got = {}
    for name in READERS:
        assert entries[name]["moves"] == "setup_s"
        assert "workloads" not in entries[name]
        got[name] = run.load_module("layer_metrics", name).read(
            {}, {}, context)
        assert isinstance(got[name], (int, float)) \
            and not isinstance(got[name], bool), name
    assert min(got["first_call_trace_s"], got["first_call_lower_s"],
               got["first_call_backend_s"], got["import_paddle_tpu_s"]) > 0
    assert got["step_loaded_from_cache"] == 0       # an empty cache
    assert got["step_pallas_instances"] == 0        # no kernel on a CPU
    assert got["setup_backend_s"] >= got["first_call_backend_s"]

    programs = monitor.xla.programs()
    assert len(programs) < monitor.xla.MAX_PROGRAMS   # none fell off
    cut = job.CHECKED_STEPS + job.WARM_STEPS
    # the trainer's step, and the reference's after the window
    assert [p["at_step_calls"] for p in programs
            if p["fun_name"] == "jit(bert_step)"] == [1]
    assert [p["at_step_calls"] > cut for p in programs
            if p["fun_name"] == "jit(step)"] == [True]
    inside = [p for p in programs if p["at_step_calls"] <= cut]
    after = [p for p in programs if p["at_step_calls"] > cut]
    assert any(p["cache_hit"] is False for p in after)
    assert got["setup_programs_compiled"] == \
        sum(1 for p in inside if p["cache_hit"] is False) >= 1
    assert got["setup_programs_compiled"] < \
        sum(1 for p in programs if p["cache_hit"] is False)
