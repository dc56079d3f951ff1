"""The ``lfm2_8b_a1b`` cell: the manifest, a CPU rehearsal of the whole
command at a tiny size, a timed path without the gates or with the
sequences run together and the float8 control coming out not correct, the
new readers giving nothing for the other configurations, and the
arithmetic of ``lfm2_costs.py`` against hand counts at the published
sizes. No number here is a device number."""
import json
import os

import numpy as np
import pytest

from benchmark import lfm2_costs as costs
from benchmark import run
from benchmark.tests import tiny, tiny_lfm2

SEED = 3_000_000_019        # more than 32 signed bits hold
NEW_READERS = ("lfm2_shortconv_ms_per_step",
               "lfm2_shortconv_kernel_ms_per_step", "lfm2_shortconv_roofline",
               "lfm2_attention_ms_per_step", "lfm2_flash_roofline",
               "lfm2_moe_ms_per_step", "lfm2_moe_padding_factor")


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 13_000_000_000)
    return module


@pytest.fixture(scope="module")
def published():
    _, cfg, traffic = run.resolve(tiny.manifest(), tiny_lfm2.CELL)
    return cfg, traffic


def _rehearse(monkeypatch, seed=SEED, trace=False, **size):
    """(result, {number: its [correct] line}) of the whole command at the
    tiny size."""
    lines = []
    monkeypatch.setattr(run, "say", lambda phase, **f: lines.append(f))
    cell, cfg, traffic, limits = tiny_lfm2.lfm2(**size)
    result = run.run_cell(tiny.manifest(), cell, cfg, traffic,
                          tiny_lfm2.roomy(limits), seed, 0.5, trace,
                          tiny.CPU, tiny.PEAKS)
    return result, {f["number"]: f for f in lines if "number" in f}


# -- the manifest ------------------------------------------------------------

def test_the_manifest_finds_the_cells_files_and_lists():
    manifest = tiny.manifest()
    cell, cfg, traffic = run.resolve(manifest, tiny_lfm2.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2_8b_a1b", "causal_pretrain_2x8k", 1)
    assert "4 x" in cell["why"] and len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "lfm2_8b_a1b")
    assert entry["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json")
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers", "vocab_size"]
    # appended behind the seven cells the benchmark had, not inserted
    assert manifest["workloads"].index(cell) >= 7
    assert traffic == {"job": "train_loop", "batch_per_chip": 2,
                       "seq_len": 8192, "recompute": True, "pool_size": 8}
    assert cfg["family"] == "lfm2_moe"
    for kind in ("families", "reference"):
        run.load_module(kind, "lfm2_moe")
    assert set(run.cell_limits(cell)) == {
        "loss_gap_first", "loss_gap_later", "first_grad_norm_gap_median",
        "first_grad_norm_gap_worst", "delta_norm_gap_median",
        "delta_norm_gap_worst"}
    listed = {m["name"]: m.get("workloads")
              for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in NEW_READERS:
        assert listed[name] == [tiny_lfm2.CELL], name
        module = run.load_module("layer_metrics", name)
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (entry["layer"], entry["unit"], entry["moves"]), name
    names = [m["name"] for m in manifest["per_layer"]]
    at = names.index(NEW_READERS[0])
    assert names[at:at + 7] == list(NEW_READERS) and "st_moe_ms_per_step" \
        in names[:at]
    for name in ("tokens_per_s_chip", "pallas_ms_per_step",
                 "flash_ms_per_step"):
        assert tiny_lfm2.CELL in listed[name], name
    # the existing moe_* / mamba_* metrics keep their lists
    for name in ("moe_ms_per_step", "moe_padding_factor", "mamba_ms_per_step",
                 "mamba_roofline", "flash_roofline"):
        assert tiny_lfm2.CELL not in listed[name], name


# -- the whole command, tiny, on the CPU -------------------------------------

def test_untraced_rehearsal_reports_the_cells_end_to_end_metrics(
        job, monkeypatch):
    result, numbers = _rehearse(monkeypatch)
    assert set(result["metrics"]) == {"tokens_per_s_chip", "step_ms",
                                      "setup_s"}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["correct"] is True, numbers
    assert result["attempted"] >= 2 and result["failed"] == 0
    json.dumps(result)


def test_traced_rehearsal_reads_the_counters_and_leaves_out_what_it_cannot(
        job, monkeypatch):
    from benchmark import reduce_trace, region_time
    from jax.profiler import ProfileData
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    before = {p: monitor.snapshot(p)
              for p in ("gated_short_conv", "flash_attention")}
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, "synthetic_trace.textproto")) as f:
        blob = ProfileData.text_proto_to_serialized_xspace(f.read())
    monkeypatch.setattr(
        reduce_trace, "reduce_dir",
        lambda path: reduce_trace.reduce(
            ProfileData.from_serialized_xspace(blob)))
    manifest = tiny.manifest()
    result, _ = _rehearse(monkeypatch, seed=13, trace=True)
    metrics = result["metrics"]
    assert set(metrics) <= {m["name"] for m in manifest["per_layer"]}
    assert metrics["compiles_in_window"]["value"] == 0
    # the CPU's trace has no device plane and its step no kernel: nothing
    # to read, nothing raised; the counters' reader has something
    for name in NEW_READERS[:6] + ("flash_ms_per_step", "fwd_ms"):
        assert name not in metrics
    assert 1.0 <= metrics["lfm2_moe_padding_factor"]["value"]
    # which path the step's call sites traced: off a TPU, the XLA ones,
    # four convolutions and one attention
    def gained(prefix, name):
        return monitor.snapshot(prefix).get(f"{prefix}.{name}", 0) \
            - before[prefix].get(f"{prefix}.{name}", 0)
    assert gained("gated_short_conv", "xla_traced") == 4
    assert gained("gated_short_conv", "kernel_traced") == 0
    assert gained("flash_attention", "xla_traced") == 1
    moe = region_time.moe_counters()
    assert moe["moe.slots_dropped"] == 0 and moe["moe.steps"] % 4 == 0


def test_the_readers_read_the_regions_and_kernels_by_name(monkeypatch,
                                                          published):
    from benchmark import program_trace
    cfg, traffic = published
    context = {"config": cfg, "traffic": traffic, "cell": {"name": "x"}}
    regions = {}
    for k, kind in enumerate(["conv", "attn", "conv", "conv", "conv"], 7):
        block = f"step/Lfm2MoeBlock_{k}/"
        if kind == "conv":
            scope = block + f"GatedShortConv_{k}"
            regions[("fwd", scope + "/Linear_3")] = 1.0
            regions[("bwd", scope + "/F.gated_short_conv")] = 2.0
        else:
            regions[("bwd", block + f"GroupedQueryAttention_{k}"
                     "/pallas_flash_attention")] = 5.0
        regions[("bwd", block + f"RoutedMoE_{k}")] = 50.0
    times = {"gated_conv_fwd": 0.008, "gated_conv_bwd": 0.012,
             "flash_fwd": 0.020, "flash_bwd": 0.040, "conv1d_fwd": 9.0,
             "moe_scatter_add": 0.030}
    out = {"regions": regions, "kernel_s": times, "steps": 10}
    monkeypatch.setattr(program_trace, "phases", lambda s, c: out)
    summary = {"steps": 10, "peaks": {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9}}
    read = {name: run.load_module("layer_metrics", name).read(
        summary, {}, context) for name in NEW_READERS[:6]}
    assert read["lfm2_shortconv_ms_per_step"] == pytest.approx(100 * 4 * 3.0)
    assert read["lfm2_attention_ms_per_step"] == pytest.approx(100 * 5.0)
    assert read["lfm2_moe_ms_per_step"] == pytest.approx(100 * 5 * 50.0)
    # the Mamba pair's kernels are not the gated pair's
    assert read["lfm2_shortconv_kernel_ms_per_step"] == pytest.approx(2.0)
    fwd, bwd = costs.shortconv_kernel_bytes(cfg, traffic)
    assert read["lfm2_shortconv_roofline"] == pytest.approx(
        100 * 4 * (fwd + bwd) / 819e9 / 0.002)
    flops, _ = costs.attention_kernel_costs(cfg, 8192, 2)
    assert read["lfm2_flash_roofline"] == pytest.approx(
        100 * flops / 197e12 / 0.006)
    assert all(v > 0 for v in read.values())


def test_the_padding_reader_reads_this_familys_counters(monkeypatch,
                                                        published):
    from benchmark import region_time
    cfg, traffic = published
    context = {"config": cfg, "traffic": traffic, "cell": {"name": "x"}}
    reader = run.load_module("layer_metrics", "lfm2_moe_padding_factor")
    # half of the 32 held experts a step on the 4,096 rung
    monkeypatch.setattr(region_time, "moe_counters", lambda: {
        "moe.steps": 4, "moe.slots_routed_here": 4 * 16384,
        "moe.rows_computed": 4 * 8 * 3072})
    assert reader.read({}, {}, context) == 1.5
    monkeypatch.setattr(region_time, "moe_counters", lambda: None)
    assert reader.read({}, {}, context) is None


# -- the seed's weights, as the configuration file assumes them -------------

def test_the_seeds_weights_have_the_assumed_scales(published):
    from benchmark.reference import lfm2_moe as reference
    cfg = dict(published[0], vocab_size=256, num_experts=2)
    assert cfg["rescale_prenorm_residual"] is True
    assert "embedding_initializer_range" not in cfg
    w = reference.init_weights(cfg, SEED)
    std = {name: float(np.std(np.asarray(a))) for name, a in w.items()}
    writer = 0.02 / np.sqrt(10.0)
    for name in ("layers.0.conv.out_proj.weight",
                 "layers.0.feed_forward.down_proj.weight",
                 "layers.1.self_attn.o_proj.weight",
                 "layers.2.feed_forward.experts_down"):
        assert std[name] == pytest.approx(writer, rel=0.02), name
    for name in ("embed_tokens.weight", "layers.0.conv.in_proj.weight",
                 "layers.1.self_attn.q_proj.weight",
                 "layers.2.feed_forward.router.weight",
                 "layers.2.feed_forward.experts_up"):
        assert std[name] == pytest.approx(0.02, rel=0.03), name
    taps = np.asarray(w["layers.0.conv.conv_weight"])
    assert np.abs(taps).max() <= 1 / np.sqrt(3) < np.abs(taps).max() * 1.01
    assert all(float(np.asarray(a).min()) == 1.0 == float(np.asarray(a).max())
               for name, a in w.items() if name.endswith("norm.weight"))
    again = reference.init_weights(cfg, SEED)
    assert all((np.asarray(w[k]) == np.asarray(again[k])).all() for k in w)


# -- `correct` has to be able to come out false -----------------------------

def _with_fault(name):
    import importlib.util
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "cell_faults", os.path.join(here, "..", "..", "scripts",
                                    "cell_faults.py"))
    faults = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(faults)
    return faults.FAULTS[name]()


def test_a_timed_path_without_the_gates_is_not_correct(job, monkeypatch):
    undo = _with_fault("gates_left_out")
    try:
        result, numbers = _rehearse(monkeypatch)
    finally:
        undo()
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["loss_gap_step1"]["holds"] is False
    assert numbers["first_grad_norm_gap_worst"]["holds"] is False


def test_a_timed_path_that_runs_the_sequences_together_is_not_correct(
        job, monkeypatch):
    """Eight sequences of 8 rows (the tiny size): the first two rows of
    seven of them read the tail of the sequence before, a fifth of the
    rows; in the cell it is two rows of 16,384, which `correct` cannot see
    (PERF.md section 7)."""
    from paddle_tpu.nn import hybrid
    before = hybrid.GatedShortConv.forward
    undo = _with_fault("sequences_run_on")
    try:
        result, numbers = _rehearse(monkeypatch)
    finally:
        undo()
    assert hybrid.GatedShortConv.forward is before
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["first_grad_norm_gap_worst"]["holds"] is False


@pytest.mark.parametrize("seed", [1, 3_000_000_003])
def test_the_float8_control_is_not_correct_and_the_reference_is(job, seed):
    cell, cfg, traffic, limits = tiny_lfm2.lfm2(rows=2, seq=64)
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


def test_the_batch_is_two_sequences_of_ids_over_the_slice(published):
    cfg, traffic = published
    family = run.load_module("families", "lfm2_moe")
    traffic = dict(traffic, chips=1)
    assert family.units_per_step(traffic) == 16384
    (ids,) = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    (again,) = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    assert (ids == again).all() and ids.shape == (2, 8192)
    assert ids.dtype == np.int32 and 0 <= ids.min() and ids.max() <= 16383
    assert family.THROUGHPUT == "tokens_per_s_chip"


def test_the_other_configurations_give_the_new_readers_nothing():
    """The parent commit's side of a traced run, and every other
    configuration's: none of this configuration's keys, no gated kernel.
    Every new reader returns None and raises nothing."""
    manifest = tiny.manifest()
    from paddle_tpu import monitor
    monitor.reset()
    monitor.device_counters.reset()
    others = [c for c in manifest["workloads"]
              if c["name"] != tiny_lfm2.CELL]
    assert len({c["config"] for c in others}) >= 6
    for cell in others:
        _, cfg, traffic = run.resolve(manifest, cell["name"])
        context = {"cell": {"name": "no.such_cell"}, "config": cfg,
                   "traffic": traffic}
        for name in NEW_READERS:
            module = run.load_module("layer_metrics", name)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, (cell["name"], name)
    # and this configuration's own, in a program without the layers
    _, cfg, traffic = run.resolve(manifest, tiny_lfm2.CELL)
    context = {"cell": {"name": "no.such_cell"}, "config": cfg,
               "traffic": traffic}
    for name in NEW_READERS:
        module = run.load_module("layer_metrics", name)
        assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                           context) is None, name


# -- the arithmetic, against hand counts (ISSUE 43, section 3) ---------------

def test_parameters_by_part_are_the_hand_counts(published):
    cfg, _ = published
    conv = costs.conv_params(cfg)
    assert conv == {"in_proj": 2048 * 6144, "taps": 2048 * 3,
                    "out_proj": 2048 * 2048}
    assert sum(conv.values()) == 16_783_360
    a = costs.attention_params(cfg)
    assert a["q_proj"] == a["o_proj"] == 2048 * 2048
    assert a["k_proj"] == a["v_proj"] == 2048 * 512 and a["head_norms"] == 128
    assert sum(a.values()) == 10_485_888
    assert costs.dense_params(cfg) == 3 * 2048 * 7168 == 44_040_192
    assert costs.expert_params(cfg) == 3 * 2048 * 1792 == 11_010_048
    assert costs.router_params(cfg) + 8 * costs.expert_params(cfg) \
        == 88_145_920
    assert costs.layer_kinds(cfg) == [
        ("conv", True), ("full_attention", False), ("conv", False),
        ("conv", False), ("conv", False)]
    assert [costs.layer_params(cfg, *k) for k in costs.layer_kinds(cfg)] \
        == [60_827_648, 98_635_904] + [104_933_376] * 3
    total = 60_827_648 + 98_635_904 + 3 * 104_933_376 + 33_554_432 + 2_048
    assert costs.total_params(cfg) == total == 507_820_160 \
        == cfg["parameters_held"]
    assert abs(16 * total / 1e9 - 8.13) < 0.01               # GB of state
    # and they are the reference's own shapes
    from benchmark.reference import lfm2_moe as ref
    assert sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values()) \
        == total
    assert sum(cfg["parameters_by_part"][k] for k in cfg["parameters_by_part"]
               if k.startswith(("layer 0", "layer 1", "embedding"))) \
        + 3 * cfg["parameters_by_part"][
            "layers 2-4 here (source 3-5: conv + experts), each"] == total


def test_flops_a_token_are_the_hand_counts(published):
    cfg, traffic = published
    seq = traffic["seq_len"]
    assert costs.head_dim(cfg) == 64 and costs.slots_here_per_row(cfg) == 1.0
    assert costs.causal_pairs(seq) == 8192 * 8193 // 2 == 33_558_528
    per = costs.forward_flops_per_token(cfg, seq)
    assert per["conv"] == 4 * (2 * 16_777_216 + 2 * 6_144 + 2 * 2_048)
    assert per["attention"] == 2 * 10_485_760
    assert per["scores"] == 32 * 4096.5 * 2 * 2 * 64
    assert per["dense"] == 2 * 44_040_192
    assert per["moe"] == 4 * (2 * 65_536 + 2 * 11_010_048)
    assert per["head"] == 2 * 2048 * 16_384
    rounded = {k: round(v / 1e6) for k, v in per.items()}
    assert rounded == {"conv": 134, "attention": 21, "scores": 34,
                       "dense": 88, "moe": 89, "head": 67}
    assert round(sum(per.values()) / 1e6) == 433
    step = costs.train_flops_per_token(cfg, seq) * 16384
    assert abs(step / 1e12 - 21.27) < 0.01                    # Tflop a step


def test_kernel_costs_are_the_hand_counts(published):
    cfg, traffic = published
    n = 2 * 8192 * 2048 * 2                     # B S C bfloat16 values
    assert n == 67_108_864
    assert costs.shortconv_kernel_bytes(cfg, traffic) == (4 * n, 7 * n)
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # four layers' pairs in 4 ms: 4 x 11 x 67 MB over 819 GB/s is 3.6 ms
    assert costs.shortconv_roofline_pct(cfg, traffic, 0.004, peaks) \
        == pytest.approx(100 * 4 * 11 * n / 819e9 / 0.004)
    assert 90.0 < costs.shortconv_roofline_pct(cfg, traffic, 0.004,
                                               peaks) < 90.2
    flops, nbytes = costs.attention_kernel_costs(cfg, 8192, 2)
    assert flops == 7 * 2 * 2 * 32 * 33_558_528 * 64
    assert abs(flops / 1e12 - 1.924) < 0.001
    q, k = 2 * 8192 * 32 * 64 * 2, 2 * 8192 * 8 * 64 * 2
    assert nbytes == 6 * q + 6 * k == 503_316_480
    assert costs.flash_roofline_pct(cfg, traffic, 0.03, peaks) \
        == pytest.approx(100 * flops / 197e12 / 0.03)
    none = dict(cfg, layer_types=["conv"] * 24)
    assert costs.flash_roofline_pct(none, traffic, 0.03, peaks) is None
    none = dict(cfg, layer_types=["full_attention"] * 24)
    assert costs.shortconv_roofline_pct(none, traffic, 0.03, peaks) is None
