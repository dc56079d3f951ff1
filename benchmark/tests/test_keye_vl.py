"""The ``keye_vl2_30b_a3b`` cell: the manifest, a CPU rehearsal of the whole
command at a tiny size, a timed path without the selection, without the
indexer's loss or with the three position rows collapsed and the float8
control coming out not correct, the new readers giving nothing for the
other configurations, and the arithmetic of ``keye_vl_costs.py`` against
hand counts at the published sizes. No number here is a device number."""
import json
import os

import numpy as np
import pytest

from benchmark import keye_vl_costs as costs
from benchmark import run
from benchmark.tests import tiny, tiny_keye

SEED = 3_000_000_019        # more than 32 signed bits hold
NEW_READERS = ("dsa_attention_ms_per_step", "dsa_select_ms_per_step",
               "dsa_kl_ms_per_step", "sel_flash_ms_per_step",
               "sel_flash_roofline", "dsa_select_roofline",
               "dsa_kl_roofline", "dsa_selected_pairs_pct",
               "sel_flash_tiles_walked_pct", "keye_moe_ms_per_step")


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 14_000_000_000)
    return module


@pytest.fixture(scope="module")
def published():
    _, cfg, traffic = run.resolve(tiny.manifest(), tiny_keye.CELL)
    return cfg, traffic


def _rehearse(monkeypatch, seed=SEED, trace=False, room=tiny_keye.roomy,
              **size):
    """(result, {number: its [correct] line}) of the whole command at the
    tiny size."""
    lines = []
    monkeypatch.setattr(run, "say", lambda phase, **f: lines.append(f))
    cell, cfg, traffic, limits = tiny_keye.keye(**size)
    result = run.run_cell(tiny.manifest(), cell, cfg, traffic, room(limits),
                          seed, 0.5, trace, tiny.CPU, tiny.PEAKS)
    return result, {f["number"]: f for f in lines if "number" in f}


# -- the manifest ------------------------------------------------------------

def test_the_manifest_finds_the_cells_files_and_lists():
    manifest = tiny.manifest()
    cell, cfg, traffic = run.resolve(manifest, tiny_keye.CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("keye_vl2_30b_a3b", "sparse_causal_16k", 1)
    assert "8 x" in cell["why"] and "23.4 %" in cell["why"] \
        and len(cell["why"]) <= 200
    entry = next(c for c in manifest["configs"]
                 if c["name"] == "keye_vl2_30b_a3b")
    assert entry["source"].startswith(
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
        "config.json") and "KeyeVL2" in entry["source"]
    assert sorted(entry["reduced"]) == sorted(cfg["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    # appended behind the eight cells the benchmark had, not inserted
    assert manifest["workloads"].index(cell) >= 8
    assert traffic == {"job": "train_loop", "batch_per_chip": 1,
                       "seq_len": 16384, "image_spans": 4,
                       "image_grid": [32, 32], "recompute": True,
                       "pool_size": 8}
    assert cfg["family"] == "keye_vl"
    for kind in ("families", "reference"):
        run.load_module(kind, "keye_vl")
    assert set(run.cell_limits(cell)) == {
        "loss_gap_first", "loss_gap_later", "first_grad_norm_gap_median",
        "first_grad_norm_gap_worst", "delta_norm_gap_median",
        "delta_norm_gap_worst"}
    listed = {m["name"]: m.get("workloads")
              for m in manifest["end_to_end"] + manifest["per_layer"]}
    for name in NEW_READERS:
        assert listed[name] == [tiny_keye.CELL], name
        module = run.load_module("layer_metrics", name)
        entry = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert (module.LAYER, module.UNIT, module.MOVES) \
            == (entry["layer"], entry["unit"], entry["moves"]), name
    # the last ten per-layer entries, in the issue's order
    assert [m["name"] for m in manifest["per_layer"][-10:]] \
        == list(NEW_READERS)
    for name in ("tokens_per_s_chip", "pallas_ms_per_step",
                 "flash_ms_per_step"):
        assert listed[name][-1] == tiny_keye.CELL, name
    for name, cells in listed.items():      # and in no other list
        if cells and tiny_keye.CELL in cells:
            assert name in NEW_READERS + ("tokens_per_s_chip",
                                          "pallas_ms_per_step",
                                          "flash_ms_per_step"), name
    # the configuration states what the issue asks of it
    for key in ("source", "reduced", "assumed", "deployment", "tower",
                "parameters"):
        assert cfg[key], key
    assert "8" in cfg["deployment"] and "LEFT OUT" in cfg["tower"]
    for key in ("rotary", "position_ids", "indexer", "selection",
                "q_chunk_size_kv_chunk_size", "indexer_loss",
                "indexer_precision", "unused_keys", "optimizer"):
        assert cfg["assumed"][key], key


# -- the rehearsal -----------------------------------------------------------

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
    from benchmark import reduce_trace
    from jax.profiler import ProfileData
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    before = monitor.snapshot("dsa.")
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
    # the step's own count: 8 keys a row of 48 (bfloat16 ties keep a few
    # more)
    exact = 100.0 * (8 * 9 // 2 + 40 * 8) / (48 * 49 // 2)
    assert exact <= metrics["dsa_selected_pairs_pct"]["value"] < exact + 1.0
    assert metrics["compiles_in_window"]["value"] == 0
    # the CPU's trace has no device plane and its step no kernel: nothing
    # to read, nothing raised
    for name in ("dsa_attention_ms_per_step", "dsa_select_ms_per_step",
                 "dsa_kl_ms_per_step", "sel_flash_ms_per_step",
                 "sel_flash_roofline", "dsa_select_roofline",
                 "dsa_kl_roofline", "sel_flash_tiles_walked_pct",
                 "keye_moe_ms_per_step", "flash_ms_per_step", "fwd_ms"):
        assert name not in metrics
    # which routes the step's two layers traced: off a TPU, the XLA ones
    seen = monitor.snapshot("dsa.")
    for op in ("select", "kl"):
        assert seen[f"dsa.{op}.xla_traced"] \
            - before.get(f"dsa.{op}.xla_traced", 0) == 2, op
        assert seen.get(f"dsa.{op}.kernel_traced", 0) \
            == before.get(f"dsa.{op}.kernel_traced", 0), op


def test_the_counter_readers_read_the_programs_counters(monkeypatch):
    from paddle_tpu import monitor
    context = {"config": {"family": "keye_vl"}}
    reader = run.load_module("layer_metrics", "sel_flash_tiles_walked_pct")
    monkeypatch.setattr(monitor, "snapshot", lambda prefix="": {
        "flash_attention.selected_tiles_walked": 5 * 16896,
        "flash_attention.selected_tiles_causal": 5 * 16896})
    assert reader.read({}, {}, context) == 100.0
    # a kernel whose bounds left a quarter of the causal tiles out
    monkeypatch.setattr(monitor, "snapshot", lambda prefix="": {
        "flash_attention.selected_tiles_walked": 5 * 12672,
        "flash_attention.selected_tiles_causal": 5 * 16896})
    assert reader.read({}, {}, context) == 75.0
    monkeypatch.setattr(monitor, "snapshot", lambda prefix="": {})
    assert reader.read({}, {}, context) is None
    reader = run.load_module("layer_metrics", "dsa_selected_pairs_pct")
    monkeypatch.setattr(monitor.device_counters, "read", lambda prefix="": {
        "dsa.pairs_selected": 50 * 30_721, "dsa.pairs_causal": 50 * 131_080})
    assert abs(reader.read({}, {}, context) - 23.437) < 0.001
    monkeypatch.setattr(monitor.device_counters, "read", lambda prefix="": {})
    assert reader.read({}, {}, context) is None


def test_the_select_roofline_counts_the_calls_its_time_covers(
        published, monkeypatch):
    """A recomputed block makes its selection twice: ten calls in 71.5 ms
    read what one call reads alone (18.9 % on the chip, PERF.md section 6),
    and a step that kept the selection, five calls in half the time, reads
    the same."""
    from benchmark import program_trace
    cfg, traffic = published
    reader = run.load_module("layer_metrics", "dsa_select_roofline")
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    context = {"config": cfg, "traffic": dict(traffic, chips=1)}

    def step_with(calls, ms):
        monkeypatch.setattr(program_trace, "kernel_ms",
                            lambda summary, context, prefix: ms)
        monkeypatch.setattr(program_trace, "ledger", lambda: {
            ("step", f"custom-call.{i}"): {
                "kernel": "dsa_select" if i < calls else "dsa_kl"}
            for i in range(calls + 5)})
        return reader.read({"peaks": peaks}, {}, context)
    twice = step_with(10, 71.5)
    assert 19.4 < twice < 19.6
    assert abs(step_with(5, 35.75) - twice) < 1e-9
    monkeypatch.setattr(program_trace, "kernel_ms", lambda *a: None)
    assert reader.read({"peaks": peaks}, {}, context) is None


def test_the_gradients_common_gap_is_the_label_logits_bfloat16_rounding(
        published):
    """What the cell's two first-gradient limits rest on (the limits file
    tells it): the loss's cotangent enters the bfloat16 head product
    through ``logits.astype("float32")``'s backward, and its one large
    element a row, ``-(1 - p_label) / sum(u)`` at the label, has the SAME
    mantissa in every predicting row, 1.33337 at this traffic (12,287
    predicting rows, 18,992 ids at random weights), which bfloat16 rounds
    to 1.3359375: +0.192 % on every gradient the language-model loss
    reaches, on every seed, where a cell that divides by a power of two
    reads 0. The readings of the median leaf have to lie about there."""
    import ml_dtypes
    cfg, traffic = published
    seq = traffic["seq_len"]
    spans = traffic["image_spans"] * traffic["image_grid"][0] \
        * traffic["image_grid"][1]
    predicting = seq - spans - 1
    assert predicting == 12_287
    label = np.float32((1.0 - 1.0 / cfg["vocab_size"]) / predicting)
    rounded = float(label.astype(ml_dtypes.bfloat16))
    bias = rounded / float(label) - 1.0
    assert abs(bias - 0.00192) < 0.00001
    # a span at the sequence's edge leaves 12,288 predicting rows: seed
    # 3000004741's batch, whose head and embedding read +0.00200
    edge = np.float32((1.0 - 1.0 / cfg["vocab_size"]) / (predicting + 1))
    assert abs(float(edge.astype(ml_dtypes.bfloat16)) / float(edge) - 1.0
               - 0.00201) < 0.00001
    assert float(np.float32(1.0 / 8192).astype(ml_dtypes.bfloat16)) \
        == 1.0 / 8192                       # the accepted causal cells
    read = run.load_json(os.path.join(
        run.HERE, "limits", tiny_keye.CELL + ".json"))["readings"]
    # the worst leaf: the common part and a leaf's own rounding
    assert bias < read["first_grad_norm_gap_worst"]["program_largest"] \
        < 1.5 * bias


# -- `correct` has to be able to come out false -----------------------------

def _faulty(monkeypatch, name, **size):
    """The rehearsal with one fault of ``scripts/cell_faults.py`` planted:
    the faults read at the cell's own size on the chip (the limits file
    has those readings) are the ones tried here."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "cell_faults", os.path.join(run.ROOT, "scripts", "cell_faults.py"))
    faults = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(faults)
    undo = faults.FAULTS[name]()
    try:
        return _rehearse(monkeypatch, **size)
    finally:
        undo()


def test_a_step_without_the_selection_is_not_correct(job, monkeypatch):
    """Dense causal attention: every causal key kept."""
    result, numbers = _faulty(monkeypatch, "selection_left_out")
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["loss_gap_step1"]["holds"] is False
    assert float(numbers["loss_gap_step1"]["value"]) > 0.005


def test_a_step_without_the_indexers_loss_is_not_correct(job, monkeypatch):
    """``L_I`` left out of the step's loss: the indexers get no gradient."""
    result, numbers = _faulty(monkeypatch, "indexer_loss_left_out")
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["loss_gap_step1"]["holds"] is False
    worst = numbers["first_grad_norm_gap_worst"]
    assert worst["holds"] is False and "indexer" in worst["note"]
    assert float(worst["value"]) > 0.99


def test_a_step_with_the_position_rows_collapsed_is_not_correct(
        job, monkeypatch):
    """Height and width ids replaced by the temporal one: one-axis
    positions over the image span. One span of 8 x 12 in 128 positions, in
    float32, the gradient held to the CELL's limits (``tiny_keye.roomy``
    says why): the sound step keeps them, the collapsed one reads 3 x the
    worst leaf's."""
    size = dict(rows=1, seq=128, spans=1, grid=(8, 12),
                compute_dtype="float32", room=tiny_keye.float32_room)
    sound, numbers = _rehearse(monkeypatch, **size)
    assert sound["correct"] is True, numbers
    result, numbers = _faulty(monkeypatch, "position_rows_collapsed", **size)
    assert result["correct"] is False and result["failed"] == 0
    worst = numbers["first_grad_norm_gap_worst"]
    assert worst["holds"] is False and "self_attn" in worst["note"]


@pytest.mark.parametrize("seed", [1, 3_000_000_003])
def test_the_float8_control_is_not_correct_and_the_reference_is(job, seed):
    cell, cfg, traffic, limits = tiny_keye.keye(rows=2, seq=96)
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


# -- the batch -----------------------------------------------------------------

def test_the_batch_is_ids_position_ids_and_label_weights_from_the_seed(
        published):
    cfg, traffic = published
    family = run.load_module("families", "keye_vl")
    traffic = dict(traffic, chips=1)
    assert family.units_per_step(traffic) == 16384
    ids, at, w = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    again = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    assert all((a == b).all() for a, b in zip((ids, at, w), again))
    assert ids.shape == w.shape == (1, 16384) and at.shape == (3, 16384)
    assert ids.dtype == at.dtype == np.int32 and w.dtype == np.float32
    assert 0 <= ids.min() and ids.max() <= 18991
    # four spans of 32 x 32, starts at multiples of 512, no overlap
    spans = np.flatnonzero(np.diff(np.r_[0, (at[0][1:] == at[0][:-1]), 0]
                                   .astype(int)) == 1)
    assert len(spans) == 4 and all(s % 512 == 0 for s in spans)
    for s in spans:
        block = at[:, s:s + 1024]
        c = block[0, 0]
        assert (block[0] == c).all()
        assert (block[1] == c + np.repeat(np.arange(32), 32)).all()
        assert (block[2] == c + np.tile(np.arange(32), 32)).all()
        if s + 1024 < 16384:        # the counter resumes at c + 32
            assert (at[:, s + 1024] == c + 32).all()
        if s:                       # the position before a span predicts nothing
            assert w[0, s - 1] == 0.0
        assert (w[0, s:s + 1023] == 0.0).all()
    # text advances all three by one; the counter ends at 12,416
    text = np.ones(16384, bool)
    for s in spans:
        text[s:s + 1024] = False
    assert (at[0][text] == at[1][text]).all() \
        and (at[0][text] == at[2][text]).all()
    assert text.sum() == 12288 and at[0].max() in (12415, 12383)
    assert at[0][text].max() <= 12415
    assert w[0, -1] == 0.0 and set(np.unique(w)) == {0.0, 1.0}
    other = family.host_batch(cfg, traffic, np.random.default_rng(SEED + 1))
    assert (other[0] != ids).any()


def test_the_other_configurations_give_the_new_readers_nothing():
    """The parent commit's side of a traced run, and every other
    configuration's: no device counters of the layer, none of this
    configuration's keys. Every new reader returns None and raises
    nothing."""
    manifest = tiny.manifest()
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    others = [c for c in manifest["workloads"] if c["name"] != tiny_keye.CELL]
    assert len({c["config"] for c in others}) == 7
    for cell in others:
        _, cfg, traffic = run.resolve(manifest, cell["name"])
        context = {"cell": {"name": "no.such_cell"}, "config": cfg,
                   "traffic": traffic}
        for name in NEW_READERS:
            if name == "sel_flash_tiles_walked_pct":    # a host counter
                continue
            module = run.load_module("layer_metrics", name)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, (cell["name"], name)
    # and this configuration's own, in a program without the layers
    _, cfg, traffic = run.resolve(manifest, tiny_keye.CELL)
    context = {"cell": {"name": "no.such_cell"}, "config": cfg,
               "traffic": traffic}
    for name in NEW_READERS:
        if name != "sel_flash_tiles_walked_pct":
            module = run.load_module("layer_metrics", name)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, name


# -- the arithmetic, against hand counts (ISSUE 47, section 3) --------------

def test_parameters_by_part_are_the_hand_counts(published):
    cfg, _ = published
    a = costs.attention_params(cfg)
    assert sum(a.values()) - a["vectors"] == 18_874_368 and a["vectors"] == 256
    ix = costs.indexer_params(cfg)
    assert ix == {"indexer_q": 2_097_152, "indexer_k": 131_072,
                  "indexer_w": 32_768, "vectors": 128}
    assert sum(ix.values()) == 2_261_120
    layer = costs.layer_params(cfg)
    assert layer["router"] == 262_144 and layer["routed"] == 75_497_472
    assert sum(layer.values()) == 96_899_456
    total = 5 * 96_899_456 + 2 * 18_992 * 2048 + 2048
    assert costs.total_params(cfg) == total == 562_290_560 \
        == cfg["parameters"]
    assert abs(16 * total / 1e9 - 9.00) < 0.01                # GB of state
    # the 4-layer floor, should 5 not fit
    assert abs(16 * (total - 96_899_456) / 1e9 - 7.45) < 0.01
    from benchmark.reference import keye_vl as ref
    assert sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values()) \
        == total


def test_selected_pairs_and_flops_a_token_are_the_hand_counts(published):
    cfg, traffic = published
    seq = traffic["seq_len"]
    assert costs.selected_pairs(16384, 2048) == 31_458_304
    assert costs.causal_pairs(16384) == 134_225_920
    assert abs(100 * 31_458_304 / 134_225_920 - 23.437) < 0.001
    assert costs.selected_pairs(1024, 2048) == costs.causal_pairs(1024)
    # by brute force at a small size: no ties, so a row keeps min(t + 1, k)
    assert costs.selected_pairs(48, 8) == sum(min(t + 1, 8)
                                              for t in range(48))
    per = costs.forward_flops_per_token(cfg, seq)
    assert per["projections"] == 5 * 2 * 18_874_368
    assert per["indexer_projections"] == 5 * 2 * 2_260_992
    assert per["scores"] == 5 * 32 * 2 * 2 * 128 * 31_458_304 / 16384
    assert per["index_scores"] == 5 * 2 * 16 * 64 * 134_225_920 / 16384
    assert per["moe"] == 5 * (2 * 262_144 + 2 * 4_718_592)
    assert per["head"] == 2 * 2048 * 18_992
    forward = sum(per.values())
    assert abs(forward / 1e9 - 0.580) < 0.001                 # Gflop a token
    index_back = 5 * 2 * 2 * 16 * 64 * 31_458_304 / 16384
    assert costs.train_flops_per_token(cfg, seq) == pytest.approx(
        3 * (forward - per["index_scores"]) + per["index_scores"]
        + index_back)
    step = costs.train_flops_per_token(cfg, seq) * seq
    assert abs(step / 1e12 - 26.41) < 0.01                    # Tflop a step


def test_kernel_costs_are_the_hand_counts(published):
    cfg, traffic = published
    seq = traffic["seq_len"]
    flops, nbytes = costs.selected_flash_costs(cfg, seq)
    assert flops == 7 * 2 * 32 * 31_458_304 * 128
    q, k = seq * 32 * 128 * 2, seq * 4 * 128 * 2
    assert nbytes == 6 * q + 6 * k + 2 * seq * seq
    flops, nbytes = costs.select_kernel_costs(cfg, seq)
    assert flops == 2 * 16 * 64 * 134_225_920
    assert nbytes == seq * ((16 * 64 + 64) * 2 + 16 * 4) + seq * (seq + 12)
    flops, nbytes = costs.kl_kernel_costs(cfg, seq)
    assert flops == 2 * 32 * 128 * 31_458_304 \
        + 3 * 2 * 16 * 64 * 31_458_304
    from benchmark import kernel_costs
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # the selected pairs' flash flops of five layers in 300 ms: 15 %
    flops, nbytes = costs.selected_flash_costs(cfg, seq)
    share, bound = kernel_costs.roofline_share_pct(5 * flops, 5 * nbytes,
                                                   0.3, peaks)
    assert bound == "flops" and 15.0 < share < 15.5
