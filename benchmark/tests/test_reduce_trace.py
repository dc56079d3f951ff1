"""The trace reduction on a small trace kept beside this file: a hand-made
one in the shape a TPU trace has (synthetic_trace.textproto says what is in
it), read through the same ``jax.profiler.ProfileData`` path as a recorded
``.xplane.pb``."""
import os

import pytest

from benchmark import reduce_trace as R
from benchmark import run

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1e-6


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "synthetic_trace.textproto")) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    path = tmp_path_factory.mktemp("trace") / "t.xplane.pb"
    path.write_bytes(blob)
    return R.reduce_dir(str(path.parent))


def test_busy_is_the_union_of_overlapping_and_nested_events(summary):
    # step 1 covers 0-90 and 95-100, step 2 the same from 130
    assert summary["busy_s"] == pytest.approx(190 * US)
    assert summary["window_s"] == pytest.approx(230 * US)
    assert summary["devices"] == ["/device:TPU:0"]


def test_union_seconds_and_gaps_directly():
    busy, gaps = R.union_seconds([(5, 6), (0, 2), (1, 3), (1.5, 2.5), (8, 9)])
    assert busy == pytest.approx(3 + 1 + 1)
    assert gaps == [(3, 5), (6, 8)]
    assert R.union_seconds([]) == (0.0, [])


def test_self_time_takes_children_out_of_the_parent(summary):
    s = summary["op_self_s"]
    # while.2 is 50 us long and encloses 15 + 25 us of children
    assert s["while (s32[], f32[8192,768])"] == pytest.approx(2 * 10 * US)
    assert s["jvp__ (f32[8192,768], f32[8192,1], f32[8192,1])"] == \
        pytest.approx(2 * 25 * US)
    assert s["copy f32[768,768]"] == pytest.approx(2 * 5 * US)
    # own times add up to the union: nothing is counted twice
    assert sum(s.values()) == pytest.approx(summary["busy_s"])
    assert summary["top_ops"][0][0] == "fusion f32[64,128,768]"


def test_kernels_are_the_mosaic_custom_calls_with_their_shapes(summary):
    assert len(summary["kernels"]) == 2
    sig, sec = summary["kernels"][0]
    assert sig == ("(f32[8192,768], f32[8192,1], f32[8192,1]) "
                   "custom-call(f32[8192,768], f32[1,768], f32[1,768])")
    assert sec == pytest.approx(25 * US)


def test_idle_gaps_are_named_by_the_span_or_the_operation_before(summary):
    gaps = summary["idle_gaps"]
    assert [round(sec / US) for _, sec in gaps] == [30, 5, 5]
    assert gaps[0][0] == "host:bench.fetch"
    assert gaps[1][0] == "in_step_after:while"
    assert gaps[2][0] == "in_step_after:while"


def _reader(name):
    return run.load_module("layer_metrics", name)


def test_readers_divide_by_the_steps_driven(summary):
    summary = dict(summary, steps=2)
    assert _reader("pallas_ms_per_step").read(summary, {}, {}) == \
        pytest.approx(25e-3)
    assert _reader("device_idle_pct").read(summary, {}, {}) == \
        pytest.approx(100 * 40 / 230)
    # a reader that finds nothing to read returns nothing
    assert _reader("pallas_ms_per_step").read(
        dict(summary, kernels=[]), {}, {}) is None
    assert _reader("step_ms_p95").read(summary, {"step_s": [0.05] * 19},
                                       {}) is None
    # rank 199 * 0.95 = 189.05: a twentieth of the way from 50 to 60 ms
    assert _reader("step_ms_p95").read(
        summary, {"step_s": [0.05] * 190 + [0.06] * 10}, {}) == \
        pytest.approx(50.5)


def test_names_lose_their_suffix_layouts_and_operand_names():
    text = ("%fusion.16 = (f32[64,128]{1,0:T(8,128)S(1)}, bf16[64,128,30522]"
            "{1,2,0:T(8,128)(2,1)}) fusion(f32[30522]{0:T(1024)S(1)} "
            "%copy-done.244, bf16[64,128,768]{2,1,0} %bitcast.1662), "
            "kind=kOutput, calls=%fused_computation")
    assert R.op_base(text) == "fusion"
    assert R.op_signature(text) == ("(f32[64,128], bf16[64,128,30522]) "
                                    "fusion(f32[30522], bf16[64,128,768])")
    assert R.op_key(text) == "fusion (f32[64,128], bf16[64,128,30522])"
    assert R.op_base("jit_traced(123)") == "jit_traced(123)"
    assert R.op_key("no instruction text") == "no instruction text"


def test_a_trace_with_no_device_plane_is_an_error(tmp_path):
    from jax.profiler import ProfileData
    blob = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }')
    (tmp_path / "t.xplane.pb").write_bytes(blob)
    with pytest.raises(ValueError, match="no device plane"):
        R.reduce_dir(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        R.reduce_dir(str(tmp_path / "nothing"))


def test_flash_roofline_counts_the_three_d_kernels_against_needed_flops():
    import json
    bench = os.path.join(HERE, "..")
    with open(os.path.join(bench, "configs", "bert_base.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench, "traffic", "pretrain_seq512.json")) as f:
        traffic = json.load(f)
    flash = "(bf16[192,512,64], f32[192,512,128]) custom-call(bf16[192,512,64])"
    norm = "(f32[8192,768], f32[8192,1]) custom-call(f32[8192,768], f32[1,768])"
    summary = {"kernels": [(flash, 0.0644), (flash, 0.05), (norm, 0.01)],
               "steps": 10,
               "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}
    context = {"config": cfg, "traffic": traffic}
    # needed: 7 products of 2*192*512*512*64 flops, 12 layers, 10 steps
    # = 5.4117e12 flops = 27.47 ms at 197 TF/s; the flash kernels took 114.4
    got = _reader("flash_roofline").read(summary, {}, context)
    assert got == pytest.approx(100 * 27.47 / 114.4, rel=1e-3)
    # layer norm alone, or a cell with no sequence length: nothing to read
    assert _reader("flash_roofline").read(
        dict(summary, kernels=[(norm, 0.01)]), {}, context) is None
    assert _reader("flash_roofline").read(
        summary, {}, {"config": cfg, "traffic": {"image_size": 224}}) is None
