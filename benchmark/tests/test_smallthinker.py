"""The ``smallthinker_21b_a3b`` cell: a CPU rehearsal of the whole command
at a tiny size, a timed path that ignores the window and the float8 control
coming out not correct, the new readers giving nothing for the other
configurations, and the arithmetic of ``smallthinker_costs.py`` against
hand counts at the published sizes. No number here is a device number."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark import smallthinker_costs as costs
from benchmark.tests import tiny, tiny_smallthinker

SEED = 3_000_000_019        # more than 32 signed bits hold
NEW_READERS = ("st_attention_ms_per_step", "st_window_attention_ms_per_step",
               "win_flash_ms_per_step", "win_flash_roofline",
               "st_global_flash_roofline", "win_flash_tiles_walked_pct",
               "st_moe_ms_per_step")


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 13_000_000_000)
    return module


@pytest.fixture(scope="module")
def published():
    _, cfg, traffic = run.resolve(tiny.manifest(), tiny_smallthinker.CELL)
    return cfg, traffic


def _rehearse(monkeypatch, seed=SEED, trace=False):
    """(result, {number: its [correct] line}) of the whole command at the
    tiny size."""
    lines = []
    monkeypatch.setattr(run, "say", lambda phase, **f: lines.append(f))
    cell, cfg, traffic, limits = tiny_smallthinker.smallthinker()
    result = run.run_cell(tiny.manifest(), cell, cfg, traffic,
                          tiny_smallthinker.roomy(limits), seed, 0.5, trace,
                          tiny.CPU, tiny.PEAKS)
    return result, {f["number"]: f for f in lines if "number" in f}


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
    before = monitor.snapshot("flash_attention")
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
    # to read, nothing raised
    for name in NEW_READERS + ("flash_ms_per_step", "fwd_ms"):
        assert name not in metrics
    # which attention path the step's four call sites traced: off a TPU,
    # the XLA one, three of them under the dense window mask
    seen = monitor.snapshot("flash_attention")
    assert seen["flash_attention.xla_traced"] \
        - before.get("flash_attention.xla_traced", 0) == 4
    assert seen.get("flash_attention.kernel_traced", 0) \
        == before.get("flash_attention.kernel_traced", 0)
    # and the experts' counters are there for the shared readers: four
    # layers a step, nothing dropped
    moe = region_time.moe_counters()
    assert moe["moe.slots_dropped"] == 0 and moe["moe.steps"] % 4 == 0


def test_the_tile_reader_reads_the_window_call_sites_alone(monkeypatch):
    from paddle_tpu import monitor
    reader = run.load_module("layer_metrics", "win_flash_tiles_walked_pct")
    # the cell's step: 6 window call sites of 28 x 252 of 1,024 tiles, and
    # 2 global ones (28 x 528) that the general counters hold too
    monkeypatch.setattr(monitor, "snapshot", lambda prefix="": {
        k: v for k, v in {
            "flash_attention.tiles": 28 * (6 * 252 + 2 * 528),
            "flash_attention.tiles_skipped": 28 * (6 * 772 + 2 * 496),
            "flash_attention.window_tiles": 6 * 28 * 252,
            "flash_attention.window_tiles_skipped": 6 * 28 * 772}.items()
        if k.startswith(prefix)})
    assert reader.read({}, {}, {}) == 100 * 252 / 1024 == 24.609375
    # a program without windowed call sites (the parent's, another
    # configuration's) counts neither: nothing to read
    monkeypatch.setattr(monitor, "snapshot", lambda prefix="": {})
    assert reader.read({}, {}, {}) is None


def test_the_window_reader_tells_the_layers_apart_by_their_order(
        monkeypatch, published):
    """Regions of eight attention scopes numbered from wherever the process
    had come to: the i-th smallest is layer i, and layers 0 and 4 are
    global."""
    from benchmark import program_trace
    cfg, traffic = published
    context = {"config": cfg, "traffic": traffic, "cell": {"name": "x"}}
    regions = {}
    for layer in range(8):
        k = 5 + layer
        scope = f"step/SmallThinkerBlock_{k}/GroupedQueryAttention_{k}"
        regions[("fwd", scope + "/Linear_3")] = 1.0
        regions[("bwd", scope + "/pallas_flash_attention")] = 2.0 + layer
        regions[("bwd", f"step/SmallThinkerBlock_{k}/RoutedMoE_{k}")] = 50.0
    out = {"regions": regions, "steps": 10}
    monkeypatch.setattr(program_trace, "phases", lambda s, c: out)
    window = run.load_module("layer_metrics",
                             "st_window_attention_ms_per_step")
    both = run.load_module("layer_metrics", "st_attention_ms_per_step")
    moe = run.load_module("layer_metrics", "st_moe_ms_per_step")
    total = sum(3.0 + layer for layer in range(8))
    assert both.read({}, {}, context) == pytest.approx(100 * total)
    assert window.read({}, {}, context) == pytest.approx(
        100 * (total - 3.0 - 7.0))
    assert moe.read({}, {}, context) == pytest.approx(100 * 8 * 50.0)
    # a step whose attention scopes are not one a layer: no guess
    del regions[("fwd", "step/SmallThinkerBlock_5/GroupedQueryAttention_5"
                 "/Linear_3")], \
        regions[("bwd", "step/SmallThinkerBlock_5/GroupedQueryAttention_5"
                 "/pallas_flash_attention")]
    assert window.read({}, {}, context) is None


def test_the_roofline_readers_divide_each_kind_by_its_own_kernels(
        monkeypatch, published):
    from benchmark import program_trace
    cfg, traffic = published
    context = {"config": cfg, "traffic": traffic, "cell": {"name": "x"}}
    times = {"flash_win_fwd": 0.060, "flash_win_bwd": 0.120,
             "flash_fwd": 0.050, "flash_bwd": 0.090,
             "moe_scatter_add": 0.030}
    monkeypatch.setattr(program_trace, "phases",
                        lambda s, c: {"kernel_s": times, "steps": 1})
    summary = {"steps": 1, "peaks": {"bf16_flops_per_s": 197e12,
                                     "hbm_bytes_per_s": 819e9}}
    read = {name: run.load_module("layer_metrics", name).read(
        summary, {}, context) for name in NEW_READERS[2:5]}
    assert read["win_flash_ms_per_step"] == pytest.approx(180.0)
    flops, _ = costs.attention_kernel_costs(cfg, 16384, 1)
    assert read["win_flash_roofline"] == pytest.approx(
        100 * 6 * flops / 197e12 / 0.180)
    flops, _ = costs.attention_kernel_costs(cfg, 16384, 0)
    assert read["st_global_flash_roofline"] == pytest.approx(
        100 * 2 * flops / 197e12 / 0.140)
    assert all(0 < v < 100 for v in read.values() if v < 150)


# -- the seed's weights, as the configuration file assumes them -------------

def test_the_seeds_weights_have_the_assumed_scales(published):
    from benchmark.reference import smallthinker as reference
    cfg = dict(published[0], vocab_size=256, num_hidden_layers=2,
               moe_num_primary_experts=2)
    assert (cfg["embedding_initializer_range"],
            cfg["rescale_prenorm_residual"]) == (1.0, True)
    w = reference.init_weights(cfg, SEED)
    std = {name: float(np.std(np.asarray(a))) for name, a in w.items()}
    assert std["embed_tokens.weight"] == pytest.approx(1.0, rel=0.01)
    for name in ("layers.1.self_attn.o_proj.weight",
                 "layers.0.block_sparse_moe.experts_down"):
        assert std[name] == pytest.approx(0.02 / 2.0, rel=0.01), name
    for name in ("layers.1.self_attn.q_proj.weight", "lm_head.weight",
                 "layers.0.block_sparse_moe.router.weight",
                 "layers.1.block_sparse_moe.experts_up"):
        assert std[name] == pytest.approx(0.02, rel=0.02), name
    again = reference.init_weights(cfg, SEED)
    assert all((np.asarray(w[k]) == np.asarray(again[k])).all() for k in w)


# -- `correct` has to be able to come out false -----------------------------

def test_a_timed_path_that_ignores_the_window_is_not_correct(job,
                                                             monkeypatch):
    """Every window layer attends causally over the whole sequence: the
    mask the portable path builds, with the lower edge dropped."""
    from paddle_tpu.ops.pallas import flash_attention_mod as flash_mod
    monkeypatch.setattr(
        flash_mod, "sliding_window_mask",
        lambda length, window: np.tril(np.ones((length, length), bool)))
    result, numbers = _rehearse(monkeypatch)
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["first_grad_norm_gap_worst"]["holds"] is False


def test_the_fault_script_plants_and_lifts_its_faults(job, monkeypatch,
                                                       tmp_path):
    """``scripts/cell_faults.py`` (the chip readings of PERF.md section 2)
    at the tiny size: each fault's numbers beside the limits, the window
    fault not correct, and the classes as they were afterwards."""
    import importlib.util
    from paddle_tpu import nn
    from paddle_tpu.nn import hybrid
    here = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "cell_faults", os.path.join(here, "..", "..", "scripts",
                                    "cell_faults.py"))
    faults = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(faults)
    cell, cfg, traffic, limits = tiny_smallthinker.smallthinker()
    monkeypatch.setattr(run, "resolve", lambda m, w: (cell, cfg, traffic))
    monkeypatch.setattr(run, "check_device", lambda c: None)
    monkeypatch.setattr(run, "cell_limits",
                        lambda c: tiny_smallthinker.roomy(limits))
    monkeypatch.setattr(run, "say", lambda phase, **f: None)
    before = (nn.RoutedMoE.forward, hybrid.GroupedQueryAttention.__init__)
    out = tmp_path / "faults.json"
    faults.main(["--workload", tiny_smallthinker.CELL, "--seed", str(SEED),
                 "--out", str(out)])
    assert before == (nn.RoutedMoE.forward,
                      hybrid.GroupedQueryAttention.__init__)
    rows = json.loads(out.read_text())["rows"]
    assert set(rows) == set(faults.FAULTS)
    held = {name: {r["number"]: r["holds"] for r in numbers}
            for name, numbers in rows.items()}
    assert held["window_ignored"]["first_grad_norm_gap_worst"] is False
    # the router's placement moves the experts' and the routers' leaves
    worst = {r["number"]: r for r in rows["router_behind_attention"]}
    assert "block_sparse_moe" in worst["first_grad_norm_gap_worst"]["note"]
    assert worst["first_grad_norm_gap_worst"]["value"] > 0.003


@pytest.mark.parametrize("seed", [1, 3_000_000_003])
def test_the_float8_control_is_not_correct_and_the_reference_is(job, seed):
    cell, cfg, traffic, limits = tiny_smallthinker.smallthinker(rows=2,
                                                                seq=64)
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


def test_the_batch_is_ids_over_the_slice_from_the_seed(published):
    cfg, traffic = published
    family = run.load_module("families", "smallthinker")
    traffic = dict(traffic, chips=1)
    assert family.units_per_step(traffic) == 16384
    (ids,) = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    (again,) = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    assert (ids == again).all() and ids.shape == (1, 16384)
    assert ids.dtype == np.int32 and 0 <= ids.min() and ids.max() <= 18991
    assert traffic["seq_len"] == cfg["max_position_embeddings"]


def test_the_other_configurations_give_the_new_readers_nothing():
    """The parent commit's side of a traced run, and every other
    configuration's: none of this configuration's keys, no windowed call
    site. Every new reader returns None and raises nothing."""
    manifest = tiny.manifest()
    listed = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    assert all(tiny_smallthinker.CELL in listed[name]
               for name in NEW_READERS)
    for name in ("tokens_per_s_chip", "pallas_ms_per_step",
                 "flash_ms_per_step"):
        lists = {m["name"]: m.get("workloads")
                 for m in manifest["end_to_end"] + manifest["per_layer"]}
        assert tiny_smallthinker.CELL in lists[name]
    from paddle_tpu import monitor
    monitor.reset()
    others = [c for c in manifest["workloads"]
              if c["name"] != tiny_smallthinker.CELL]
    assert others
    for cell in others:
        _, cfg, traffic = run.resolve(manifest, cell["name"])
        context = {"cell": {"name": "no.such_cell"}, "config": cfg,
                   "traffic": traffic}
        for name in NEW_READERS:
            module = run.load_module("layer_metrics", name)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, (cell["name"], name)
    # and this configuration's own, in a program without the layers
    _, cfg, traffic = run.resolve(manifest, tiny_smallthinker.CELL)
    context = {"cell": {"name": "no.such_cell"}, "config": cfg,
               "traffic": traffic}
    for name in NEW_READERS:
        module = run.load_module("layer_metrics", name)
        assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                           context) is None, name


# -- the arithmetic, against hand counts (ISSUE 41, "The configuration") ----

def test_the_file_holds_the_catalogs_numbers_and_names_its_cuts(published):
    cfg, _ = published
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_ffn_hidden_size"],
            cfg["moe_num_active_primary_experts"],
            cfg["sliding_window_size"], cfg["rope_theta"]) \
        == (2560, 128, 28, 4, 768, 6, 4096, 1500000)
    assert cfg["rope_layout"] == cfg["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13                      # the published 52 entries
    assert set(cfg["reduced"]) == {"num_hidden_layers",
                                   "moe_num_primary_experts", "vocab_size"}
    assert (cfg["num_hidden_layers"], cfg["num_hidden_layers_published"],
            cfg["moe_num_primary_experts"],
            cfg["moe_num_primary_experts_published"], cfg["vocab_size"],
            cfg["vocab_size_published"]) == (8, 52, 8, 64, 18992, 151936)
    assert 8 * 18992 == 151936 and "8 chips" not in cfg["deployment"][:3]
    assert "group of 8" in cfg["deployment"]
    assert costs.windowed_layers(cfg) == [0, 1, 1, 1, 0, 1, 1, 1]


def test_parameters_by_part_are_the_hand_counts(published):
    cfg, _ = published
    a = costs.attention_params(cfg)
    assert a["q_proj"] == 2560 * 3584 == a["o_proj"]
    assert a["k_proj"] == a["v_proj"] == 2560 * 512
    assert sum(a.values()) == 20_971_520
    assert costs.expert_params(cfg) == 3 * 2560 * 768 == 5_898_240
    layer = costs.layer_params(cfg)
    assert layer["router"] == 163_840 and layer["vectors"] == 5_120
    assert layer["routed"] == 8 * 5_898_240
    assert sum(layer.values()) == 68_326_400
    total = 8 * 68_326_400 + 2 * 18_992 * 2560 + 2560
    assert costs.total_params(cfg) == total == 643_852_800
    assert abs(16 * total / 1e9 - 10.30) < 0.01               # GB of state
    # and they are the reference's own shapes
    from benchmark.reference import smallthinker as ref
    assert sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values()) \
        == total


def test_pairs_and_flops_a_token_are_the_hand_counts(published):
    cfg, traffic = published
    seq = traffic["seq_len"]
    assert costs.allowed_pairs(seq, 4096) == 58_722_304
    assert costs.allowed_pairs(seq) == 134_225_920
    assert costs.allowed_pairs(100, 200) == costs.allowed_pairs(100) == 5050
    # by brute force at a small size: the mask's own count
    from benchmark.reference import smallthinker as ref
    at = np.arange(96)
    for window in (1, 33, 96, None):
        assert int(np.asarray(ref.allowed(at, at, window)).sum()) \
            == costs.allowed_pairs(96, window)
    assert abs(58_722_304 / 134_225_920 - 0.4375) < 1e-4
    per = costs.forward_flops_per_token(cfg, seq)
    assert per["projections"] == 8 * 2 * 20_971_520
    assert per["scores"] * seq == 28 * (
        2 * 134_225_920 + 6 * 58_722_304) * 2 * 2 * 128
    assert costs.slots_here_per_row(cfg) == 0.75
    assert per["moe"] == 8 * (2 * 163_840 + 2 * 0.75 * 5_898_240)
    assert per["head"] == 2 * 2560 * 18_992
    step = costs.train_flops_per_token(cfg, seq) * seq
    assert abs(step / 1e12 - 51.58) < 0.01                    # Tflop a step
    shares = {k: round(100 * v / sum(per.values())) for k, v in per.items()}
    assert shares == {"projections": 32, "scores": 52, "moe": 7, "head": 9}


def test_attention_kernel_costs_are_the_hand_counts(published):
    """28 heads over the allowed pairs of 16,384 rows: seven products of
    2 x 128 flops a pair; Q-sized arrays 117.4 MB, K-sized 16.8 MB (4
    key/value heads)."""
    cfg, traffic = published
    win, nbytes = costs.attention_kernel_costs(cfg, traffic["seq_len"], 1)
    assert win == 7 * 2 * 28 * 58_722_304 * 128
    assert abs(win / 1e12 - 2.946) < 0.001
    glob, same = costs.attention_kernel_costs(cfg, traffic["seq_len"], 0)
    assert glob == 7 * 2 * 28 * 134_225_920 * 128 and same == nbytes
    q, k = 16384 * 28 * 128 * 2, 16384 * 4 * 128 * 2
    assert nbytes == 6 * q + 6 * k == 805_306_368
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.flash_roofline_pct(cfg, traffic, 1, 0.2, peaks) \
        == pytest.approx(100 * 6 * win / 197e12 / 0.2)
    assert 44.8 < costs.flash_roofline_pct(cfg, traffic, 1, 0.2, peaks) < 44.9
    assert costs.flash_roofline_pct(dict(cfg, sliding_window_layout=[1] * 8),
                                    traffic, 0, 0.2, peaks) is None
    # the walked tiles hold more pairs than are allowed: 252 tiles of 512 x
    # 512 a head against 58.7 M
    assert 252 * 512 * 512 / costs.allowed_pairs(16384, 4096) > 1.12
