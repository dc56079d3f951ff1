"""The ``nemotron_h`` cell: a CPU rehearsal of the whole command at a tiny
size, the float8 control coming out not correct, and the arithmetic of
``nemotron_h_costs.py`` against hand counts. No number here is a device
number."""
import json
import os

import pytest

from benchmark import nemotron_h_costs as costs
from benchmark import run
from benchmark.tests import tiny, tiny_nemotron

SEED = 3_000_000_019        # more than 32 signed bits hold


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 12_000_000_000)
    return module


@pytest.fixture(scope="module")
def published():
    manifest = tiny.manifest()
    _, cfg, traffic = run.resolve(manifest, tiny_nemotron.CELL)
    return cfg, traffic


def test_untraced_rehearsal_reports_the_cells_end_to_end_metrics(job):
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny_nemotron.nemotron()
    result = run.run_cell(manifest, cell, cfg, traffic, tiny.roomy(limits),
                          SEED, 0.5, False, tiny.CPU, tiny.PEAKS)
    assert set(result["metrics"]) == {"tokens_per_s_chip", "step_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["correct"] is True
    assert result["attempted"] >= 2 and result["failed"] == 0
    json.dumps(result)


def test_traced_rehearsal_reads_the_counters_and_leaves_out_what_it_cannot(
        job, monkeypatch):
    from benchmark import reduce_trace
    from jax.profiler import ProfileData
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "synthetic_trace.textproto")) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    monkeypatch.setattr(
        reduce_trace, "reduce_dir",
        lambda path: reduce_trace.reduce(
            ProfileData.from_serialized_xspace(blob)))
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny_nemotron.nemotron()
    result = run.run_cell(manifest, cell, cfg, traffic, tiny.roomy(limits),
                          11, 0.5, True, tiny.CPU, tiny.PEAKS)
    metrics = result["metrics"]
    assert set(metrics) <= {m["name"] for m in manifest["per_layer"]}
    # the program's counters: read after the trainer is freed
    tokens = traffic["batch_per_chip"] * traffic["seq_len"]
    even = tokens * 2 * costs.slots_here_per_token(cfg)     # two E layers
    assert 0.5 * even < metrics["moe_slots_per_step"]["value"] < 1.6 * even
    assert metrics["moe_dropped_slots"]["value"] == 0
    assert 1.0 <= metrics["moe_load_max_over_mean"]["value"] <= 4.0
    # 48 tokens are under the ladder's first rung: one rung of 48 rows an
    # expert, 4 held experts, two E layers
    assert metrics["moe_rows_computed_per_step"]["value"] == 2 * 4 * tokens
    assert metrics["compiles_in_window"]["value"] == 0
    # the CPU's trace has no device plane: nothing to read, nothing raised
    for name in ("mamba_ms_per_step", "moe_roofline", "fwd_ms",
                 "flash_ms_per_step", "gqa_flash_roofline"):
        assert name not in metrics


def test_a_program_without_the_layers_gives_the_new_readers_nothing():
    """The parent commit's side of a traced run: no region of the classes,
    no device counters. Every new reader returns None and raises
    nothing."""
    manifest = tiny.manifest()
    cell, cfg, traffic, _ = tiny_nemotron.nemotron()
    context = {"cell": {"name": "no.such_cell"}, "config": cfg,
               "traffic": traffic}
    new = [m["name"] for m in manifest["per_layer"]
           if m.get("workloads") == [tiny_nemotron.CELL]]
    assert len(new) == 10
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    for name in new:
        module = run.load_module("layer_metrics", name)
        assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                           context) is None, name


def test_region_time_adds_up_the_instances_of_a_class():
    from benchmark import region_time
    out = {"regions": {
        ("fwd", "M_0/NemotronHBlock_0/Mamba2Mixer_0/Linear_0"): 1.0,
        ("bwd", "M_0/NemotronHBlock_2/Mamba2Mixer_1/F.ssd_scan"): 2.0,
        ("bwd", "M_0/NemotronHBlock_2/Mamba2Mixer_1"): 0.5,
        ("fwd", "M_0/NemotronHBlock_1/RoutedMoE_0/F.moe_experts"): 4.0,
        ("fwd", "M_0/NotMamba2Mixer_0/Linear_0"): 8.0}}
    assert region_time.class_seconds(out, "Mamba2Mixer") == 3.5
    assert region_time.class_seconds(out, "RoutedMoE") == 4.0
    assert region_time.class_seconds(out, "GroupedQueryAttention") == 0


@pytest.mark.parametrize("seed", [1, 3_000_000_003])
def test_the_float8_control_is_not_correct_and_the_reference_is(job, seed):
    cell, cfg, traffic, limits = tiny_nemotron.nemotron(rows=2, seq=64)
    family = run.load_module("families", cfg["family"])
    ref = family.reference
    traffic = dict(traffic, chips=1)
    batches = job.make_pool(family, cfg, traffic, seed)[:job.CHECKED_STEPS]
    hyper = cfg["assumed"]["optimizer"]
    want = ref.train(cfg, hyper, seed, batches)
    control = ref.train(cfg, hyper, seed, batches, precision="float8")
    lines = []
    leaves = ref.compared_leaves(cfg)
    ok = job.compare(control, want, limits, leaves,
                     lambda phase, **f: lines.append(f))
    assert ok is False
    assert [f["number"] for f in lines if not f["holds"]], lines
    assert job.compare(want, want, limits, leaves,
                       lambda *a, **k: None) is True


# -- the arithmetic, against hand counts (ISSUE 27, Motivation) -------------

def test_parameters_by_layer_kind_are_the_hand_counts(published):
    cfg, _ = published
    mamba = costs.layer_params(cfg, "M")
    assert mamba["in_proj"] == 2688 * 10304 == 27_697_152
    assert mamba["out_proj"] == 4096 * 2688 == 11_010_048
    assert mamba["conv"] == 6144 * 4 + 6144
    assert sum(mamba.values()) == 38_744_896                  # 38.75 M
    attention = costs.layer_params(cfg, "*")
    assert attention["q_proj"] == attention["o_proj"] == 2688 * 4096
    assert attention["k_proj"] == attention["v_proj"] == 2688 * 256
    assert sum(attention.values()) == 23_399_040              # 23.40 M
    moe = costs.layer_params(cfg, "E")
    assert costs.expert_params(cfg) == 2 * 2688 * 1856 == 9_977_856
    assert moe["routed"] == 8 * 9_977_856
    assert moe["shared"] == 2 * 2688 * 3712 and moe["router"] == 2688 * 128
    assert sum(moe.values()) == 100_125_312                   # 100.13 M
    # 4 M + 4 E + 1 * + embedding and head slices + the final norm
    total = 4 * 38_744_896 + 4 * 100_125_312 + 23_399_040 \
        + 2 * 16384 * 2688 + 2688
    assert costs.total_params(cfg) == total == 666_962_944
    assert abs(16 * total / 1e9 - 10.67) < 0.01               # GB of state
    # and they are the reference's own shapes
    from benchmark.reference import nemotron_h as ref
    sizes = ref.param_shapes(cfg)
    count = 0
    for shape in sizes.values():
        n = 1
        for dim in shape:
            n *= dim
        count += n
    assert count == total


def test_flops_a_token_are_the_hand_counts(published):
    cfg, traffic = published
    seq = traffic["seq_len"]
    per = costs.forward_flops_per_token(cfg, seq)
    scan = 2 * 128 * 128 * 8 + 2 * 128 * 4096 + 2 * 2 * 128 * 4096
    assert per["M"] == 4 * (2 * (27_697_152 + 11_010_048) + scan
                            + 2 * 4 * 6144)
    assert per["*"] == 2 * (23_399_040 - 2688) + 2 * seq * 4096
    assert costs.slots_here_per_token(cfg) == 0.375
    assert per["E"] == 4 * (2 * (2688 * 128 + 2 * 2688 * 3712)
                            + 2 * 0.375 * 9_977_856)
    assert per["head"] == 2 * 2688 * 16384
    forward = sum(per.values())
    assert abs(forward / 1e9 - 0.72) < 0.005                  # Gflop a token
    shares = {k: round(100 * v / forward) for k, v in per.items()}
    assert shares == {"M": 45, "E": 27, "*": 16, "head": 12}
    step = costs.train_flops_per_token(cfg, seq) * seq
    assert step == 3 * forward * seq
    assert abs(step / 1e12 - 17.6) < 0.05                     # Tflop a step


def test_attention_kernel_costs_are_the_hand_counts(published):
    """32 query heads x 8,192 x 8,192 x 128, causal: 137.4 Gflop a
    product, 2 forward and 5 backward; Q-sized arrays 67.1 MB, K/V-sized
    (2 heads) 4.2 MB, six of each."""
    cfg, traffic = published
    flops, nbytes = costs.attention_kernel_costs(cfg, traffic["seq_len"])
    assert flops == 7 * 2 * 32 * 8192 * 8192 * 128 / 2
    assert abs(flops / 1e12 - 1.924) < 0.001
    assert nbytes == 6 * (32 + 2) * 8192 * 128 * 2
    from benchmark import kernel_costs
    assert flops == 0.5 * sum(kernel_costs.flash_attention_flops(
        1, 32, 8192, 8192, 128, b) for b in (False, True))
    share, bound = kernel_costs.roofline_share_pct(
        flops, nbytes, 0.025, {"bf16_flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9})
    assert bound == "flops" and 39 < share < 39.1


def test_a_roofline_share_is_least_time_over_time_taken(published):
    cfg, traffic = published
    tokens = traffic["seq_len"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    flops = 3 * 4 * tokens * costs.layer_forward_flops_per_token(
        cfg, "M", tokens)
    least = flops / 197e12
    share, bound = costs.kind_roofline_pct(cfg, "M", tokens, tokens,
                                           2 * least, peaks)
    assert bound == "flops" and abs(share - 50.0) < 1e-9
    # more slots routed here, more flops for the same time
    few, _ = costs.kind_roofline_pct(cfg, "E", tokens, tokens, 0.05, peaks,
                                     slots_here=0.1)
    many, _ = costs.kind_roofline_pct(cfg, "E", tokens, tokens, 0.05, peaks,
                                      slots_here=1.0)
    assert few < many < 100
    with pytest.raises(ValueError):
        costs.layer_params(cfg, "X")
