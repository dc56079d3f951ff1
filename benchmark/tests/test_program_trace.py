"""The join of a device trace with the program's instruction ledger, on a
small trace and a small optimized HLO kept beside this file
(scoped_trace.textproto says what is in it; scoped_step.hlo.txt is the
program it ran), both read through the paths a real run takes:
``jax.profiler.ProfileData`` and ``monitor.profile.instruction_ledger``."""
import os

import pytest

from benchmark import program_trace as T
from benchmark import run
from benchmark.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6
CELL = {"name": "tiny.cell"}
SCOPES = {"step": "root", "bwd": "phase", "Linear_0": "layer",
          "LayerNorm_0": "layer", "F.layer_norm": "functional",
          "opt.SGD": "optimizer"}
PHASE_OF = {"bwd": "bwd", "opt.SGD": "opt"}
NEW_METRICS = ("fwd_ms", "bwd_ms", "optimizer_ms", "cross_phase_ms",
               "unscoped_device_pct", "flash_ms_per_step",
               "layer_norm_ms_per_step", "host_collect_ms",
               "host_execute_ms")


def _write_trace(root, text):
    from jax.profiler import ProfileData
    d = os.path.join(root, CELL["name"])
    os.makedirs(d)
    with open(os.path.join(d, "t.xplane.pb"), "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


def _rows():
    from paddle_tpu.monitor import profile
    if not hasattr(profile, "instruction_ledger"):
        pytest.skip("this program has no monitor.profile.instruction_ledger")
    with open(os.path.join(HERE, "scoped_step.hlo.txt")) as f:
        rows = profile.instruction_ledger(label="jit.step", hlo=f.read(),
                                          scope_map=SCOPES,
                                          phase_map=PHASE_OF)
    return {(r["module"], r["name"]): r for r in rows}


@pytest.fixture()
def scoped(tmp_path, monkeypatch):
    """The scoped trace where a run leaves its own, and the fixture's HLO
    as the executable the monitor kept."""
    with open(os.path.join(HERE, "scoped_trace.textproto")) as f:
        _write_trace(str(tmp_path), f.read())
    monkeypatch.setattr(T, "TRACE_ROOT", str(tmp_path))
    monkeypatch.setattr(T, "ledger", _rows)
    monkeypatch.setattr(T, "_cache", {})
    said = []
    out = T.phases({"steps": 2, "peaks": tiny.PEAKS}, {"cell": CELL},
                   say=said.append)
    return out, said


def _context():
    return {"steps": 2, "peaks": tiny.PEAKS}, {}, {"cell": CELL}


def test_own_time_is_joined_by_module_and_instruction_name(scoped):
    out, _ = scoped
    regions = out["regions"]
    # fusion.7 of jit_step is the forward matmul: 20 us a step
    assert regions[("fwd", "Linear_0")] == pytest.approx(2 * 20 * US)
    # the Pallas kernel, found by the name= in its op_name
    assert regions[("bwd", "LayerNorm_0/F.layer_norm")] == \
        pytest.approx(2 * 25 * US)
    assert out["kernel_s"] == {"layer_norm_bwd": pytest.approx(2 * 25 * US)}
    # copy.4 has a namesake in no module of the ledger: jit_other's is unknown
    assert ("jit_other", "copy.4") not in _rows()
    assert out["steps"] == 2


def test_a_fusion_holding_two_phases_is_split_by_modelled_time(scoped):
    out, _ = scoped
    peaks = tiny.PEAKS
    bwd = max(2 * 256 * 128 * 512 / peaks["bf16_flops_per_s"],
              2 * (512 * 256 + 512 * 128) / peaks["hbm_bytes_per_s"])
    opt = max(3 * 256 * 128 / peaks["bf16_flops_per_s"],
              4 * 4 * 256 * 128 / peaks["hbm_bytes_per_s"])
    share = bwd / (bwd + opt)
    assert 0.8 < share < 0.9
    fusion = 2 * 40 * US
    assert out["regions"][("bwd", "Linear_0")] == pytest.approx(fusion * share)
    # the update's share of the fusion, and the prefetch that serves it
    assert out["phase_s"]["opt"] == pytest.approx(
        fusion * (1 - share) + 2 * 5 * US)
    assert out["served_s"] == pytest.approx(2 * 5 * US)
    # its whole time rests on the model, and nothing else does
    assert out["cross_s"] == pytest.approx(fusion)
    assert list(out["cross"]) == ["multiply_subtract_fusion"]
    sec, split = out["cross"]["multiply_subtract_fusion"]
    assert sec == pytest.approx(fusion)
    assert split == {"bwd": pytest.approx(fusion * share),
                     "opt": pytest.approx(fusion * (1 - share))}


def test_an_unknown_instruction_is_unscoped_and_the_phases_add_up(scoped):
    out, said = scoped
    # fusion.99 (10 us a step) and the other module's copy.4 (10 us once)
    assert out["phase_s"]["none"] == pytest.approx((2 * 10 + 10) * US)
    assert out["regions"][("none", T.UNKNOWN)] == \
        pytest.approx(out["phase_s"]["none"])
    assert out["busy_s"] == pytest.approx((2 * 100 + 10) * US)
    assert sum(out["phase_s"].values()) == pytest.approx(out["busy_s"])
    head = said[0]
    assert head.startswith("[phases] fwd=0.020 bwd=")
    assert "sum_vs_busy=0.000%" in head and "DO_NOT_ADD_UP" not in head
    # the note adds the instances of a layer class up
    assert "[phases] region bwd 0.025 ms LayerNorm_*/F.layer_norm" in said
    assert T.model_parts({("bwd", "Enc_3/Linear_14"): 1.0,
                          ("bwd", "Enc_11/Linear_46"): 2.0,
                          ("fwd", "Enc_3/Linear_14"): 4.0,
                          ("opt", "opt.AdamW"): 8.0}) == {
        ("bwd", "Enc_*/Linear_*"): 3.0, ("fwd", "Enc_*/Linear_*"): 4.0,
        ("opt", "opt.AdamW"): 8.0}
    assert any(line.startswith("[phases] cross multiply_subtract_fusion")
               and "modelled_split bwd=" in line for line in said)
    assert "[phases] kernel layer_norm_bwd 0.025 ms" in said


def test_the_readers_report_per_step(scoped):
    summary, counters, context = _context()
    read = {name: run.load_module("layer_metrics", name).read(
        summary, counters, context) for name in NEW_METRICS}
    out, _ = scoped
    assert read["fwd_ms"] == pytest.approx(0.020)
    assert read["bwd_ms"] == pytest.approx(
        1e3 * out["phase_s"]["bwd"] / 2)
    assert read["optimizer_ms"] == pytest.approx(
        1e3 * out["phase_s"]["opt"] / 2)
    assert read["cross_phase_ms"] == pytest.approx(0.040)
    assert read["unscoped_device_pct"] == pytest.approx(100 * 30 / 210)
    assert read["layer_norm_ms_per_step"] == pytest.approx(0.025)
    assert read["flash_ms_per_step"] is None       # the step holds none
    busy_ms = 1e3 * out["busy_s"] / 2
    assert read["fwd_ms"] + read["bwd_ms"] + read["optimizer_ms"] + \
        read["unscoped_device_pct"] / 100 * busy_ms == pytest.approx(busy_ms)


def test_host_spans_are_read_by_name_from_the_loops_thread(scoped):
    summary, counters, context = _context()
    # 4 and 6 us inside bench.dispatch; the other thread's 50 us is not
    # of the traced steps
    assert run.load_module("layer_metrics", "host_collect_ms").read(
        summary, counters, context) == pytest.approx(0.005)
    assert run.load_module("layer_metrics", "host_execute_ms").read(
        summary, counters, context) == pytest.approx(0.005)
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(os.path.join(
        T.TRACE_ROOT, CELL["name"], "t.xplane.pb"))
    events = T.host_events(profile, T.STEP_SPANS + (T.ENCLOSING_SPAN,))
    report = T.span_report(events)
    assert report["jit.collect"] == {"count": 3, "inside": 2,
                                     "seconds_inside": pytest.approx(10 * US)}
    assert report["jit.execute"]["inside"] == 2
    assert report["jit.writeback"]["inside"] == 2
    covered = sum(report[n]["seconds_inside"] for n in T.STEP_SPANS)
    assert covered / report["enclosing_s"] == pytest.approx(22 / 26)
    assert [e for e in T.host_events(profile, ("tensor.to_host",))] == \
        [("tensor.to_host", pytest.approx(1e-3 + 100 * US),
          pytest.approx(1e-3 + 130 * US), "python3")]


HOST_ONLY = """
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python3" timestamp_ns: 1000000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.dispatch" } }
}
"""


def test_every_new_reader_returns_none_without_a_device_plane(
        tmp_path, monkeypatch):
    _write_trace(str(tmp_path), HOST_ONLY)
    monkeypatch.setattr(T, "TRACE_ROOT", str(tmp_path))
    monkeypatch.setattr(T, "ledger", _rows)
    monkeypatch.setattr(T, "_cache", {})
    summary, counters, context = _context()
    for name in NEW_METRICS:
        assert run.load_module("layer_metrics", name).read(
            summary, counters, context) is None, name


def test_every_new_reader_returns_none_without_a_trace_or_a_ledger(
        tmp_path, monkeypatch, scoped):
    summary, counters, context = _context()
    # a program that kept no executable, or has no instruction_ledger
    monkeypatch.setattr(T, "_cache", {})
    monkeypatch.setattr(T, "ledger", lambda: None)
    for name in NEW_METRICS[:7]:
        assert run.load_module("layer_metrics", name).read(
            summary, counters, context) is None, name
    # no trace where the run would have left one
    monkeypatch.setattr(T, "TRACE_ROOT", str(tmp_path / "nothing"))
    for name in NEW_METRICS:
        assert run.load_module("layer_metrics", name).read(
            summary, counters, context) is None, name


def test_the_ledger_is_none_for_a_program_that_kept_no_executable():
    from paddle_tpu import monitor
    monitor.xla.reset()
    assert T.ledger() is None


def test_names_are_taken_off_the_trace_as_it_prints_them():
    assert T.instruction_name(
        "%fusion.123 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == \
        "fusion.123"
    assert T.instruction_name("%jvp_layer_norm_fwd_.1 = (f32[8,8]) "
                              "custom-call(%x)") == "jvp_layer_norm_fwd_.1"
    assert T.module_name("jit_bert_step(16801841977803347210)") == \
        "jit_bert_step"
    assert T.module_name("jit_bert_step") == "jit_bert_step"
