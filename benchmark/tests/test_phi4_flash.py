"""The ``phi4_mini_flash`` cell: the manifest, a CPU rehearsal of the whole
command at a tiny size, a timed path with a planted fault coming out not
correct, the new readers giving nothing for the other configurations, and
the arithmetic of ``phi4_flash_costs.py`` against hand counts at the
published sizes. No number here is a device number."""
import json
import os

import numpy as np
import pytest

from benchmark import phi4_flash_costs as costs
from benchmark import run
from benchmark.tests import tiny, tiny_phi4

SEED = 3_000_000_023        # more than 32 signed bits hold
NEW_READERS = ("phi4_mamba_ms_per_step", "sscan_ms_per_step",
               "sscan_roofline", "diff_attention_ms_per_step",
               "phi4_win_flash_roofline", "phi4_flash_roofline",
               "phi4_win_flash_tiles_walked_pct", "gmu_ms_per_step",
               "phi4_mlp_ms_per_step")
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 13_000_000_000)
    return module


@pytest.fixture(scope="module")
def published():
    _, cfg, traffic = run.resolve(tiny.manifest(), tiny_phi4.CELL)
    return cfg, traffic


def _rehearse(monkeypatch, seed=SEED, trace=False, **size):
    """(result, {number: its [correct] line}) of the whole command at the
    tiny size."""
    lines = []
    monkeypatch.setattr(run, "say", lambda phase, **f: lines.append(f))
    cell, cfg, traffic, limits = tiny_phi4.phi4(**size)
    result = run.run_cell(tiny.manifest(), cell, cfg, traffic,
                          tiny_phi4.roomy(limits), seed, 0.5, trace,
                          tiny.CPU, tiny.PEAKS)
    return result, {f["number"]: f for f in lines if "number" in f}


# -- the manifest ------------------------------------------------------------

def test_the_manifest_finds_the_cells_files_and_lists():
    manifest = tiny.manifest()
    cell, cfg, traffic = run.resolve(manifest, tiny_phi4.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("phi4_mini_flash", "causal_pretrain", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "phi4_mini_flash")
    assert entry["source"] == "https://huggingface.co/microsoft/" \
        "Phi-4-mini-flash-reasoning/blob/main/config.json"
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    # the driver refuses a why or a source beyond 200 characters, or one
    # that does not print on one line (PR 50's first check: a why of 206)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    for text in (entry["why"], entry["source"], cell["why"]):
        assert 1 <= len(text) <= 200 and text.isprintable(), text
    # appended behind the nine cells the benchmark had, not inserted
    assert manifest["workloads"].index(cell) == 9
    assert traffic == {"job": "train_loop", "batch_per_chip": 1,
                       "seq_len": 8192, "recompute": True, "pool_size": 8}
    assert cfg["family"] == "phi4_flash"
    for kind in ("families", "reference"):
        run.load_module(kind, "phi4_flash")
    listed = {m["name"]: m.get("workloads")
              for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in NEW_READERS:
        assert listed[name] == [tiny_phi4.CELL], name
        module = run.load_module("layer_metrics", name)
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (entry["layer"], entry["unit"], entry["moves"]), name
    names = [m["name"] for m in manifest["per_layer"]]
    assert names[-9:] == list(NEW_READERS)
    for name in ("tokens_per_s_chip", "pallas_ms_per_step",
                 "flash_ms_per_step", "layer_norm_ms_per_step"):
        assert listed[name][-1] == tiny_phi4.CELL, name
    for name in ("win_flash_roofline", "mamba_roofline", "flash_roofline",
                 "lfm2_flash_roofline"):
        assert tiny_phi4.CELL not in listed[name], name


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


def test_the_readers_read_the_regions_kernels_and_counters_by_name(
        monkeypatch, published):
    from benchmark import program_trace
    from paddle_tpu import monitor
    cfg, traffic = published
    context = {"config": cfg, "traffic": traffic, "cell": {"name": "x"}}
    regions = {}
    kinds = ["MambaMixer", "DifferentialAttention", "MambaMixer",
             "DifferentialAttention", "GatedMemoryUnit",
             "DifferentialAttention"]
    for k, kind in enumerate(kinds, 3):
        block = f"step/Phi4FlashBlock_{k}/"
        regions[("fwd", block + f"{kind}_{k}/Linear_1")] = 1.0
        regions[("bwd", block + f"{kind}_{k}/F.x")] = 2.0
        regions[("bwd", block + f"GatedMLP_{k}")] = 20.0
    times = {"selective_scan_fwd": 0.010, "selective_scan_bwd": 0.030,
             "flash_fwd": 0.020, "flash_bwd": 0.040, "flash_win_fwd": 0.002,
             "flash_win_bwd": 0.004, "conv1d_fwd": 9.0}
    out = {"regions": regions, "kernel_s": times, "steps": 10}
    monkeypatch.setattr(program_trace, "phases", lambda s, c: out)
    monkeypatch.setattr(monitor, "snapshot", lambda prefix: {
        "flash_attention.window_tiles": 40 * 31,
        "flash_attention.window_tiles_needed": 40 * 16})
    summary = {"steps": 10, "peaks": PEAKS}
    read = {name: run.load_module("layer_metrics", name).read(
        summary, {}, context) for name in NEW_READERS}
    assert read["phi4_mamba_ms_per_step"] == pytest.approx(100 * 2 * 3.0)
    assert read["diff_attention_ms_per_step"] == pytest.approx(100 * 3 * 3.0)
    assert read["gmu_ms_per_step"] == pytest.approx(100 * 3.0)
    assert read["phi4_mlp_ms_per_step"] == pytest.approx(100 * 6 * 20.0)
    assert read["sscan_ms_per_step"] == pytest.approx(4.0)
    flops, nbytes = costs.scan_kernel_costs(cfg, traffic)
    assert read["sscan_roofline"] == pytest.approx(
        100 * 2 * nbytes / 819e9 / 0.004)
    win, _ = costs.attention_kernel_costs(cfg, "window_attention", 8192)
    assert read["phi4_win_flash_roofline"] == pytest.approx(
        100 * win / 197e12 / 0.0006)
    full, _ = costs.attention_kernel_costs(cfg, "full_attention", 8192)
    assert read["phi4_flash_roofline"] == pytest.approx(
        100 * 2 * full / 197e12 / 0.006)
    assert read["phi4_win_flash_tiles_walked_pct"] == pytest.approx(193.75)
    assert all(v > 0 for v in read.values())


def test_the_other_configurations_give_the_new_readers_nothing():
    """The parent commit's side of a traced run, and every other
    configuration's: none of this configuration's layers, kernels or
    counters. Every new reader returns None and raises nothing."""
    manifest = tiny.manifest()
    from paddle_tpu import monitor
    monitor.reset()
    others = [c for c in manifest["workloads"]
              if c["name"] != tiny_phi4.CELL]
    assert len({c["config"] for c in others}) >= 8
    cells = [c["name"] for c in others] + [tiny_phi4.CELL]
    for name in cells:
        _, cfg, traffic = run.resolve(manifest, name)
        context = {"cell": {"name": "no.such_cell"}, "config": cfg,
                   "traffic": traffic}
        for reader in NEW_READERS:
            module = run.load_module("layer_metrics", reader)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, (name, reader)


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


@pytest.mark.parametrize("fault", [
    "lambda_taken_as_zero", "memory_behind_the_gate",
    "cross_reads_its_own_stream"])
def test_a_timed_path_with_a_planted_fault_is_not_correct(job, monkeypatch,
                                                          fault):
    undo = _with_fault(fault)
    try:
        result, numbers = _rehearse(monkeypatch)
    finally:
        undo()
    assert result["correct"] is False and result["failed"] == 0
    assert [n for n, f in numbers.items() if f["holds"] is False], numbers


def test_the_float8_control_is_not_correct_and_the_reference_is(job):
    cell, cfg, traffic, limits = tiny_phi4.phi4(rows=2, seq=64)
    family = run.load_module("families", cfg["family"])
    ref = family.reference
    traffic = dict(traffic, chips=1)
    batches = job.make_pool(family, cfg, traffic, SEED)[:job.CHECKED_STEPS]
    hyper = cfg["assumed"]["optimizer"]
    want = ref.train(cfg, hyper, SEED, batches)
    control = ref.train(cfg, hyper, SEED, batches, precision="float8")
    lines = []
    leaves = ref.compared_leaves(cfg)
    ok = job.compare(control, want, limits, leaves,
                     lambda phase, **f: lines.append(f))
    assert ok is False
    assert [f["number"] for f in lines if not f["holds"]], lines
    assert job.compare(want, want, limits, leaves,
                       lambda *a, **k: None) is True


# -- the seed's weights, as the configuration file assumes them -------------

def test_the_seeds_weights_have_the_assumed_scales(published):
    from benchmark.reference import phi4_flash as reference
    cfg = dict(published[0], vocab_size=256)
    assert cfg["rescale_prenorm_residual"] is True
    w = reference.init_weights(cfg, SEED)
    std = {name: float(np.std(np.asarray(a))) for name, a in w.items()}
    writer = 0.02 / np.sqrt(12.0)
    for name in ("layers.0.mixer.out_proj.weight",
                 "layers.1.mixer.o_proj.weight",
                 "layers.4.mixer.out_proj.weight",
                 "layers.5.mlp.down_proj.weight"):
        assert std[name] == pytest.approx(writer, rel=0.02), name
    for name in ("embed_tokens.weight", "layers.0.mixer.in_proj.weight",
                 "layers.0.mixer.x_proj.weight",
                 "layers.0.mixer.dt_proj.weight",
                 "layers.3.mixer.k_proj.weight",
                 "layers.4.mixer.in_proj.weight",
                 "layers.5.mixer.q_proj.weight"):
        assert std[name] == pytest.approx(0.02, rel=0.03), name
    assert std["layers.1.mixer.lambda_q1"] == pytest.approx(0.1, rel=0.3)
    a_log = np.asarray(w["layers.2.mixer.A_log"])
    assert np.allclose(np.exp(a_log), np.arange(1, 17)[None, :])
    dt = np.log1p(np.exp(np.asarray(w["layers.2.mixer.dt_proj.bias"])))
    assert 0.001 <= dt.min() < 0.0012 and 0.09 < dt.max() <= 0.1001
    assert np.abs(np.asarray(w["layers.0.mixer.conv_weight"])).max() <= 0.5
    for name, a in w.items():
        a = np.asarray(a)
        if name.endswith(("layernorm.weight", "subln.weight", ".D")):
            assert a.min() == 1.0 == a.max(), name
        elif name.endswith(".bias") and "conv" not in name \
                and "dt_proj" not in name:
            assert a.min() == 0.0 == a.max(), name


def test_the_batch_is_one_sequence_of_ids_over_the_slice(published):
    cfg, traffic = published
    family = run.load_module("families", "phi4_flash")
    traffic = dict(traffic, chips=1)
    assert family.units_per_step(traffic) == 8192
    (ids,) = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    assert ids.shape == (1, 8192) and ids.dtype == np.int32
    assert 0 <= ids.min() and 25000 < ids.max() <= 25007
    assert family.THROUGHPUT == "tokens_per_s_chip"


# -- the arithmetic, against hand counts (ISSUE 50, Tentpole 1 and 3) --------

def test_parameters_are_the_published_size_and_the_cuts(published):
    cfg, _ = published
    assert sum(costs.mamba_params(cfg).values()) == 41_241_600
    assert sum(costs.attention_params(cfg).values()) == 19_668_864
    assert sum(costs.attention_params(cfg, cross=True).values()) \
        == 13_112_704
    assert sum(costs.memory_unit_params(cfg).values()) == 26_214_400
    assert costs.mlp_params(cfg) == 78_643_200
    assert costs.kinds_held(cfg) == {
        "mamba": 2, "window_attention": 1, "full_attention": 1,
        "memory_unit": 1, "cross_attention": 1}
    cut = 2 * 41_241_600 + 2 * 19_668_864 + 26_214_400 + 13_112_704 \
        + 6 * (78_643_200 + 10_240) + 25_008 * 2_560 + 5_120
    assert costs.total_params(cfg) == cut == 697_094_272 \
        == cfg["parameters_held"]
    assert abs(16 * cut / 1e9 - 11.15) < 0.01                # GB of state
    whole = costs.published(cfg)
    assert costs.kinds_held(whole) == {
        "mamba": 9, "window_attention": 8, "full_attention": 1,
        "memory_unit": 7, "cross_attention": 7}
    assert costs.total_params(whole) == 9 * 41_241_600 + 9 * 19_668_864 \
        + 7 * 26_214_400 + 7 * 13_112_704 + 32 * (78_643_200 + 10_240) \
        + 200_064 * 2_560 + 5_120 == 3_852_562_944
    from benchmark.reference import phi4_flash as ref
    assert sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values()) \
        == cut
    parts = cfg["parameters_by_part"]
    by_kind = [v for v in parts.values() if isinstance(v, int)]
    assert 2 * by_kind[0] + 2 * by_kind[1] + by_kind[2] + by_kind[3] \
        + 6 * by_kind[4] + by_kind[5] + by_kind[6] == cut


def test_flops_and_kernel_costs_are_the_hand_counts(published):
    cfg, traffic = published
    assert costs.causal_pairs(8192) == 33_558_528
    assert costs.window_pairs(8192, 512) == 512 * 513 // 2 + 7680 * 512 \
        == 4_063_488
    per = costs.forward_flops_per_token(cfg, 8192)
    assert per["mlp"] == 6 * 2 * 78_643_200
    assert per["scan"] == 2 * 9 * 5120 * 16
    assert per["head"] == 2 * 2560 * 25_008
    assert per["scores"] == pytest.approx(
        40 * (4_063_488 + 2 * 33_558_528) / 8192 * 2 * 3 * 64)
    step = costs.train_flops_per_token(cfg, 8192) * 8192
    assert abs(step / 1e12 - 37.57) < 0.01                   # Tflop a step
    n = 8192 * 5120 * 2
    flops, nbytes = costs.scan_kernel_costs(cfg, traffic)
    assert nbytes == 13 * n + 3 * 8192 * 32 * 2
    assert flops == 4 * 9 * 8192 * 5120 * 16
    # bytes bound at the MXU's rate: 1.09 GB a layer in 1.33 ms
    assert costs.sscan_roofline_pct(cfg, traffic, 0.02, PEAKS) \
        == pytest.approx(100 * 2 * nbytes / 819e9 / 0.02)
    flops, nbytes = costs.attention_kernel_costs(cfg, "full_attention", 8192)
    assert flops == 22 * 64 * 40 * 33_558_528
    q, o, k, v = (8192 * 2 * w for w in (40 * 64, 40 * 128, 20 * 64,
                                         10 * 128))
    assert nbytes == (q + k + v + o) + (2 * q + 2 * k + 2 * v + 2 * o)
    assert costs.flash_roofline_pct(cfg, traffic, ("window_attention",),
                                    0.001, PEAKS) == pytest.approx(
        100 * 22 * 64 * 40 * 4_063_488 / 197e12 / 0.001)
    none = dict(cfg, first_layer=0, num_hidden_layers=1)
    assert costs.flash_roofline_pct(none, traffic, ("full_attention",),
                                    0.03, PEAKS) is None
    none = dict(cfg, first_layer=17, num_hidden_layers=1)
    assert costs.sscan_roofline_pct(none, traffic, 0.03, PEAKS) is None
