"""The ``joyai_llm_flash`` cell: a CPU rehearsal of the whole command at a
tiny size, the float8 control coming out not correct, the new readers
giving nothing for a program without the layers, and the arithmetic of
``joyai_llm_flash_costs.py`` against hand counts at the published sizes. No
number here is a device number."""
import json
import os

import pytest

from benchmark import joyai_llm_flash_costs as costs
from benchmark import run
from benchmark.tests import tiny, tiny_joyai

SEED = 3_000_000_019        # more than 32 signed bits hold
NEW_READERS = ("mla_attention_ms_per_step", "mla_flash_roofline",
               "moe_gated_roofline", "mtp_ms_per_step", "moe_padding_factor")


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 13_000_000_000)
    return module


@pytest.fixture(scope="module")
def published():
    _, cfg, traffic = run.resolve(tiny.manifest(), tiny_joyai.CELL)
    return cfg, traffic


def test_untraced_rehearsal_reports_the_cells_end_to_end_metrics(job):
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny_joyai.joyai()
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
    traced_before = monitor.snapshot("flash_attention").get(
        "flash_attention.xla_traced", 0)
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "synthetic_trace.textproto")) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    monkeypatch.setattr(
        reduce_trace, "reduce_dir",
        lambda path: reduce_trace.reduce(
            ProfileData.from_serialized_xspace(blob)))
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny_joyai.joyai()
    result = run.run_cell(manifest, cell, cfg, traffic, tiny.roomy(limits),
                          13, 0.5, True, tiny.CPU, tiny.PEAKS)
    metrics = result["metrics"]
    assert set(metrics) <= {m["name"] for m in manifest["per_layer"]}
    # the program's counters, the three expert layers (two blocks and the
    # module) added up: 48 tokens are under the ladder's first rung, so
    # each of 4 held experts computes 48 rows whatever it drew
    # (``moe_dropped_slots`` and ``moe_load_max_over_mean`` do not list
    # this cell: PERF.md section 7 row 32; the counters they read are asked
    # here)
    from benchmark import region_time
    seen = region_time.moe_counters()
    assert seen["moe.slots_dropped"] == 0
    assert "moe_dropped_slots" not in metrics
    even = costs.slots_here_per_token(cfg)              # 0.75 of a slot
    assert 4 / (1.6 * even) < metrics["moe_padding_factor"]["value"] \
        < 4 / (0.5 * even)
    assert metrics["compiles_in_window"]["value"] == 0
    # the CPU's trace has no device plane: nothing to read, nothing raised
    for name in ("mla_attention_ms_per_step", "mla_flash_roofline",
                 "moe_gated_roofline", "mtp_ms_per_step", "moe_ms_per_step",
                 "flash_ms_per_step", "fwd_ms"):
        assert name not in metrics
    # which attention path the step's four call sites traced: off a TPU,
    # the XLA one
    seen = monitor.snapshot("flash_attention")
    assert seen["flash_attention.xla_traced"] - traced_before == 4
    assert "flash_attention.kernel_traced" not in seen


def test_a_program_without_the_layers_gives_the_new_readers_nothing():
    """The parent commit's side of a traced run, and every other
    configuration's: no region of the classes, no device counters, none of
    this configuration's keys. Every new reader returns None and raises
    nothing."""
    manifest = tiny.manifest()
    listed = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    assert all(listed[name] == [tiny_joyai.CELL] for name in NEW_READERS)
    _, nemotron, traffic = run.resolve(
        manifest, "nemotron3_nano_30b_a3b.causal_pretrain")
    _, joyai, _ = run.resolve(manifest, tiny_joyai.CELL)
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    for cfg in (nemotron, joyai):
        context = {"cell": {"name": "no.such_cell"}, "config": cfg,
                   "traffic": traffic}
        for name in NEW_READERS:
            module = run.load_module("layer_metrics", name)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, name


def test_the_nemotron_cell_s_readers_give_this_configuration_nothing():
    """The ten readers that list the nemotron cell alone (seven read its
    pattern or its costs; ``moe_ms_per_step``, ``moe_load_max_over_mean``
    and ``moe_dropped_slots`` read only the class ``RoutedMoE`` and the
    ``moe.*`` counters and wait for a ``benchmark`` PR to list this cell,
    PERF.md section 7 row 32): each returns None and raises nothing for a
    program without the layers under this configuration's keys too."""
    manifest = tiny.manifest()
    nemotron_cell = "nemotron3_nano_30b_a3b.causal_pretrain"
    theirs = [m["name"] for m in manifest["per_layer"]
              if m.get("workloads") == [nemotron_cell]]
    assert len(theirs) == 10
    assert {"moe_ms_per_step", "moe_load_max_over_mean",
            "moe_dropped_slots"} < set(theirs)
    _, cfg, traffic = run.resolve(manifest, tiny_joyai.CELL)
    context = {"cell": {"name": "no.such_cell"}, "config": cfg,
               "traffic": traffic}
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    for name in theirs:
        module = run.load_module("layer_metrics", name)
        assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                           context) is None, name


def test_the_module_s_time_is_its_block_and_its_head_pass():
    from benchmark import region_time
    out = {"regions": {
        ("fwd", "J_0/MultiTokenPredictor_0/MultiHeadLatentAttention_5"): 1.0,
        ("bwd", "J_0/MultiTokenPredictor_0/RoutedMoE_4/F.moe_experts"): 2.0,
        ("bwd", "J_0/MultiTokenPredictor_0/Linear_40"): 0.25,
        ("fwd", "J_0/SharedHead_0/Linear_41"): 0.5,
        ("fwd", "J_0/JoyAIFlashBlock_1/MultiHeadLatentAttention_1"): 4.0,
        ("fwd", "J_0/Linear_41"): 8.0}}
    assert region_time.class_seconds(out, "MultiTokenPredictor") == 3.25
    assert region_time.class_seconds(out, "SharedHead") == 0.5
    assert region_time.class_seconds(out, "MultiHeadLatentAttention") == 5.0
    assert region_time.class_seconds(out, "RoutedMoE") == 2.0


@pytest.mark.parametrize("seed", [1, 3_000_000_003])
def test_the_float8_control_is_not_correct_and_the_reference_is(job, seed):
    cell, cfg, traffic, limits = tiny_joyai.joyai(rows=2, seq=64)
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


# -- the arithmetic, against hand counts (ISSUE 31, section 3) --------------

def test_parameters_by_part_are_the_hand_counts(published):
    cfg, _ = published
    assert costs.layer_kinds(cfg) == "DEEEEP"
    mla = costs.mla_params(cfg)
    assert mla["q_a_proj"] == 2048 * 1536 and mla["q_b_proj"] == 1536 * 6144
    assert mla["kv_a_proj_with_mqa"] == 2048 * 576
    assert mla["kv_b_proj"] == 512 * 8192 and mla["o_proj"] == 4096 * 2048
    assert sum(mla.values()) == 26_347_520                    # 26.35 M
    assert costs.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    dense = costs.layer_params(cfg, "D")
    assert dense["dense"] == 3 * 2048 * 7168
    assert sum(dense.values()) == 70_391_808                  # 70.4 M
    moe = costs.layer_params(cfg, "E")
    assert moe["router"] == 2048 * 256 and moe["shared"] == 4_718_592
    assert moe["routed"] == 16 * 4_718_592
    assert sum(moe.values()) == 107_091_968                   # 107.1 M
    mtp = costs.layer_params(cfg, "P")
    assert sum(mtp.values()) == 107_091_968 + 2 * 2048 * 2048 + 3 * 2048
    total = 70_391_808 + 4 * 107_091_968 + sum(mtp.values()) \
        + 2 * 16160 * 2048 + 2048
    assert costs.total_params(cfg) == total == 680_439_808
    assert abs(16 * total / 1e9 - 10.89) < 0.01               # GB of state
    # a whole layer's 256 experts under AdamW do not fit one chip
    assert 16 * 256 * costs.expert_params(cfg) / 1e9 > 19.3
    # and they are the reference's own shapes
    from benchmark.reference import joyai_llm_flash as ref
    count = 0
    for shape in ref.param_shapes(cfg).values():
        n = 1
        for dim in shape:
            n *= dim
        count += n
    assert count == total
    with pytest.raises(ValueError):
        costs.ffn_params(cfg, "X")


def test_flops_a_token_are_the_hand_counts(published):
    cfg, traffic = published
    seq = traffic["seq_len"]
    per = costs.forward_flops_per_token(cfg, seq)
    mla = 2 * (26_347_520 - 2048) + 32 * seq * (192 + 128)
    assert costs.mla_forward_flops_per_token(cfg, seq) == mla
    assert per["mla"] == 5 * mla
    assert per["dense"] == 2 * 3 * 2048 * 7168
    assert costs.slots_here_per_token(cfg) == 0.5
    expert_layer = 2 * (2048 * 256 + 4_718_592) + 2 * 0.5 * 4_718_592
    assert per["moe"] == 4 * expert_layer
    assert per["head"] == 2 * 2048 * 16160
    assert per["mtp"] == mla + expert_layer + per["head"] \
        + 2 * 2 * 2048 * 2048
    forward = sum(per.values())
    assert abs(forward / 1e9 - 1.133) < 0.001                 # Gflop a token
    shares = {k: round(100 * v / forward) for k, v in per.items()}
    assert shares == {"mla": 60, "dense": 8, "moe": 5, "mtp": 21, "head": 6}
    step = costs.train_flops_per_token(cfg, seq) * seq
    assert step == 3 * forward * seq
    assert abs(step / 1e12 - 27.84) < 0.01                    # Tflop a step


def test_attention_kernel_costs_are_the_hand_counts(published):
    """32 heads x 8,192 x 8,192, causal: forward QK^T at 192 and PV at 128;
    backward three products at 192 and two at 128. Q-sized arrays 100.7 MB,
    O-sized 67.1 MB; K at 32 x 128 + the one rotary head of 64."""
    cfg, traffic = published
    flops, nbytes = costs.attention_kernel_costs(cfg, traffic["seq_len"])
    unit = 2 * 32 * 8192 * 8192 / 2
    assert flops == unit * (192 + 128 + 3 * 192 + 2 * 128)
    assert abs(flops / 1e12 - 2.474) < 0.001
    q, o = 8192 * 32 * 192 * 2, 8192 * 32 * 128 * 2
    k = 8192 * (32 * 128 + 64) * 2
    assert nbytes == 3 * q + 3 * k + 6 * o
    # at one head size they are kernel_costs' own, halved for the mask
    from benchmark import kernel_costs
    same = dict(cfg, qk_nope_head_dim=64, qk_rope_head_dim=64)
    flops_128, _ = costs.attention_kernel_costs(same, 8192)
    assert flops_128 == 0.5 * sum(kernel_costs.flash_attention_flops(
        1, 32, 8192, 8192, 128, b) for b in (False, True))
    share, bound = kernel_costs.roofline_share_pct(
        6 * flops, 6 * nbytes, 0.25, {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    assert bound == "flops" and 30.1 < share < 30.2


def test_the_expert_layers_roofline_share_is_least_time_over_time_taken(
        published):
    cfg, traffic = published
    tokens = traffic["seq_len"]
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # five expert layers (four blocks and the module): 1.87 Tflop against
    # 5.5 GB on an even router, so flops are the bound by a little
    assert costs.moe_train_bytes(cfg, tokens) == \
        3 * (2048 * 256 + 17 * 4_718_592) * 4 + 4 * tokens * 2048 * 2
    flops = 3 * 5 * tokens * (2 * (2048 * 256 + 4_718_592) + 4_718_592)
    least = flops / 197e12
    assert least > 5 * costs.moe_train_bytes(cfg, tokens) / 819e9
    share, bound = costs.moe_roofline_pct(cfg, tokens, 4 * least, peaks)
    assert bound == "flops" and abs(share - 25.0) < 1e-9
    few, _ = costs.moe_roofline_pct(cfg, tokens, 0.2, peaks, slots_here=0.1)
    many, _ = costs.moe_roofline_pct(cfg, tokens, 0.2, peaks, slots_here=8.0)
    assert few < many < 100
