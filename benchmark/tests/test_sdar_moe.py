"""The ``sdar_30b_a3b_chat`` cell: a CPU rehearsal of the whole command at
a tiny size, three broken steps and the float8 control coming out not
correct, the new readers giving nothing for the other configurations, and
the arithmetic of ``sdar_moe_costs.py`` against hand counts at the
published sizes. No number here is a device number."""
import json
import os

import numpy as np
import pytest

from benchmark import run
from benchmark import sdar_moe_costs as costs
from benchmark.tests import tiny, tiny_sdar

SEED = 3_000_000_019        # more than 32 signed bits hold
NEW_READERS = ("bd_attention_ms_per_step", "bd_flash_roofline",
               "bd_flash_tiles_walked_pct", "sdar_moe_ms_per_step",
               "bd_masked_rows_per_step")


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 11_000_000_000)
    return module


@pytest.fixture(scope="module")
def published():
    _, cfg, traffic = run.resolve(tiny.manifest(), tiny_sdar.CELL)
    return cfg, traffic


def _rehearse(monkeypatch, seed=SEED, trace=False):
    """(result, {number: its [correct] line}) of the whole command at the
    tiny size."""
    lines = []
    monkeypatch.setattr(run, "say", lambda phase, **f: lines.append(f))
    cell, cfg, traffic, limits = tiny_sdar.sdar()
    result = run.run_cell(tiny.manifest(), cell, cfg, traffic,
                          tiny_sdar.roomy(limits), seed, 0.5, trace,
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
    from benchmark import reduce_trace
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
    # the step's own count of masked positions: the noise level is uniform
    # over [0.05, 1], so about 0.525 of the 2 x 24 data tokens a step
    assert 0.3 * 48 < metrics["bd_masked_rows_per_step"]["value"] < 0.75 * 48
    assert metrics["compiles_in_window"]["value"] == 0
    # the CPU's trace has no device plane and its step no kernel: nothing
    # to read, nothing raised
    for name in ("bd_attention_ms_per_step", "bd_flash_roofline",
                 "bd_flash_tiles_walked_pct", "sdar_moe_ms_per_step",
                 "flash_ms_per_step", "fwd_ms"):
        assert name not in metrics
    # which attention path the step's two call sites traced: off a TPU, the
    # XLA one, under the dense mask
    seen = monitor.snapshot("flash_attention")
    assert seen["flash_attention.xla_traced"] \
        - before.get("flash_attention.xla_traced", 0) == 2
    assert seen.get("flash_attention.kernel_traced", 0) \
        == before.get("flash_attention.kernel_traced", 0)
    # and the experts' counters are there for the shared readers
    from benchmark import region_time
    assert region_time.moe_counters()["moe.slots_dropped"] == 0


def test_the_tile_reader_reads_the_dispatchs_counters(monkeypatch):
    from paddle_tpu import monitor
    reader = run.load_module("layer_metrics", "bd_flash_tiles_walked_pct")
    context = {"config": {"family": "sdar_moe"}}
    monkeypatch.setattr(monitor, "snapshot", lambda prefix="": {
        "flash_attention.tiles": 5 * 9216,
        "flash_attention.tiles_masked": 5 * 1536,
        "flash_attention.tiles_skipped": 5 * 23552})
    assert reader.read({}, {}, context) == 28.125
    # the parent's program counts no skipped tiles: nothing to read
    monkeypatch.setattr(monitor, "snapshot", lambda prefix="": {
        "flash_attention.tiles": 26112})
    assert reader.read({}, {}, context) is None


# -- the seed's weights, as the configuration file assumes them -------------

def test_the_seeds_weights_have_the_assumed_scales(published):
    """Embedding at unit scale, the two writers of a layer divided by
    sqrt(2 x layers), everything else at initializer_range; a configuration
    without the three keys (tests/test_sdar_moe.py's) draws 0.02 throughout."""
    from benchmark.reference import sdar_moe as reference
    cfg = dict(published[0], vocab_size=256, mask_token_id=255,
               num_hidden_layers=2, num_experts=2)
    assert (cfg["embedding_initializer_range"],
            cfg["rescale_prenorm_residual"],
            cfg["experts_numbered_by_mask_rank"]) == (1.0, True, True)
    w = reference.init_weights(cfg, SEED)
    std = {name: float(np.std(np.asarray(a))) for name, a in w.items()}
    assert std["embed_tokens.weight"] == pytest.approx(1.0, rel=0.01)
    for name in ("layers.1.self_attn.o_proj.weight",
                 "layers.0.mlp.experts_down"):
        assert std[name] == pytest.approx(0.02 / 2.0, rel=0.01), name
    for name in ("layers.1.self_attn.q_proj.weight", "lm_head.weight",
                 "layers.0.mlp.router.weight", "layers.1.mlp.experts_up"):
        assert std[name] == pytest.approx(0.02, rel=0.02), name
    plain = {k: v for k, v in cfg.items()
             if k not in ("embedding_initializer_range",
                          "rescale_prenorm_residual",
                          "experts_numbered_by_mask_rank")}
    old = reference.init_weights(plain, SEED)
    assert float(np.std(np.asarray(old["embed_tokens.weight"]))) \
        == pytest.approx(0.02, rel=0.01)
    # the numbering moves the router's columns and nothing else
    for name in w:
        if name.endswith(("router.weight", "embed_tokens.weight",
                          "o_proj.weight", "experts_down")):
            continue
        np.testing.assert_array_equal(np.asarray(w[name]),
                                      np.asarray(old[name]), err_msg=name)
    a, b = (np.asarray(x["layers.0.mlp.router.weight"]) for x in (w, old))
    assert sorted(map(tuple, a.T)) == sorted(map(tuple, b.T))


@pytest.mark.parametrize("seed", [3, SEED, 2_147_483_659])
def test_the_mask_tokens_rows_choose_no_expert_held_here(published, seed):
    """Experts numbered by the mask token's rank, lowest first: in every
    layer the router gives the mask token's embedding rising scores over
    the expert numbers, so its top-k are the last k and the share held
    (the first ``num_experts``) gets none of the masked positions' rows."""
    from benchmark.reference import sdar_moe as reference
    from benchmark.reference.nemotron_h import _rms_norm
    cfg = dict(published[0], vocab_size=256, mask_token_id=255,
               num_hidden_layers=3, num_experts=2)
    w = reference.init_weights(cfg, seed)
    row = _rms_norm(w["embed_tokens.weight"][cfg["mask_token_id"]],
                    np.ones(cfg["hidden_size"], np.float32),
                    cfg["rms_norm_eps"])
    for i in range(cfg["num_hidden_layers"]):
        scores = np.asarray(row) @ np.asarray(
            w[f"layers.{i}.mlp.router.weight"], np.float64)
        assert np.all(np.diff(scores) > 0), i
        chosen, _ = reference.route(cfg, row[None],
                                    w[f"layers.{i}.mlp.router.weight"])
        held = cfg["first_expert_held"] + 16      # the published share
        assert np.asarray(chosen).min() >= held
        assert sorted(np.asarray(chosen)[0]) == list(range(120, 128))


# -- `correct` has to be able to come out false -----------------------------

def test_a_step_without_the_clean_copys_keys_is_not_correct(job, monkeypatch):
    """Every row sees its own copy's block alone: the mask the portable
    path builds, with the clean keys of earlier blocks dropped."""
    from paddle_tpu.ops.pallas import flash_attention_mod as flash_mod

    def own_block_alone(length, block):
        at = np.arange(2 * length)
        clean, blk = at >= length, (at % length) // block
        return (clean[None, :] == clean[:, None]) \
            & (blk[None, :] == blk[:, None])

    monkeypatch.setattr(flash_mod, "block_diffusion_mask", own_block_alone)
    result, numbers = _rehearse(monkeypatch)
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["first_grad_norm_gap_worst"]["holds"] is False


def test_a_step_without_the_noise_weights_is_not_correct(job, monkeypatch):
    """1 at every masked position in place of 1 / t."""
    family = run.load_module("families", "sdar_moe")
    build = family.build

    def build_unweighted(cfg, traffic, weights):
        trainer = build(cfg, traffic, weights)
        whole = trainer.step

        def step(clean, noisy, w):
            import paddle_tpu as pt
            return whole(clean, noisy, pt.to_tensor(
                (w.numpy() > 0).astype(np.float32)))

        trainer.step = step
        return trainer

    monkeypatch.setattr(family, "build", build_unweighted)
    result, numbers = _rehearse(monkeypatch)
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["loss_gap_step1"]["holds"] is False
    assert float(numbers["loss_gap_step1"]["value"]) > 0.1


def test_a_loss_in_bfloat16_is_not_correct(job, monkeypatch):
    """One float32 island in the compute dtype: the loss's soft-max
    statistics and sum."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import ops
    from paddle_tpu.dispatch import apply

    def in_bfloat16(z, y, w):
        def impl(z, y, w):
            z = z.astype(jnp.bfloat16)
            picked = jnp.take_along_axis(z, y[..., None], -1)[..., 0]
            ce = jax.scipy.special.logsumexp(z, axis=-1) - picked
            return (jnp.sum(w.astype(jnp.bfloat16) * ce)
                    / ce.size).astype(jnp.float32)
        return apply(impl, (z, y, w), name="block_diffusion_loss")

    monkeypatch.setattr(ops.loss, "block_diffusion_loss", in_bfloat16)
    result, numbers = _rehearse(monkeypatch)
    assert result["correct"] is False and result["failed"] == 0
    assert numbers["loss_gap_step1"]["holds"] is False


@pytest.mark.parametrize("seed", [1, 3_000_000_003])
def test_the_float8_control_is_not_correct_and_the_reference_is(job, seed):
    cell, cfg, traffic, limits = tiny_sdar.sdar(rows=2, seq=64)
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


def test_the_batch_is_clean_ids_noisy_ids_and_weights_from_the_seed(
        published):
    cfg, traffic = published
    family = run.load_module("families", "sdar_moe")
    traffic = dict(traffic, chips=1)
    assert family.units_per_step(traffic) == 8192      # data tokens
    clean, noisy, w = family.host_batch(cfg, traffic,
                                        np.random.default_rng(SEED))
    again = family.host_batch(cfg, traffic, np.random.default_rng(SEED))
    assert all((a == b).all() for a, b in zip((clean, noisy, w), again))
    assert clean.shape == noisy.shape == w.shape == (1, 8192)
    assert clean.dtype == noisy.dtype == np.int32 and w.dtype == np.float32
    assert clean.max() <= 18990 and cfg["mask_token_id"] == 18991
    masked = noisy != clean
    assert (noisy[masked] == 18991).all() and ((w > 0) == masked).all()
    # one noise level a block of 4: a block's weights are one number
    by_block, hit = w.reshape(2048, 4), masked.reshape(2048, 4)
    level = by_block.max(1, keepdims=True)
    assert (by_block[hit] == np.broadcast_to(level, hit.shape)[hit]).all()
    assert 1.0 <= w[masked].min() and w.max() <= 1 / 0.05
    assert abs(masked.mean() - 0.525) < 0.02


def test_the_other_configurations_give_the_new_readers_nothing():
    """The parent commit's side of a traced run, and every other
    configuration's: no device counters of the model, none of this
    configuration's keys. Every new reader returns None and raises
    nothing."""
    manifest = tiny.manifest()
    listed = {m["name"]: m.get("workloads") for m in manifest["per_layer"]}
    assert all(listed[name] == [tiny_sdar.CELL] for name in NEW_READERS)
    from paddle_tpu import monitor
    monitor.device_counters.reset()
    others = [c for c in manifest["workloads"] if c["name"] != tiny_sdar.CELL]
    assert len({c["config"] for c in others}) == 4
    for cell in others:
        _, cfg, traffic = run.resolve(manifest, cell["name"])
        context = {"cell": {"name": "no.such_cell"}, "config": cfg,
                   "traffic": traffic}
        for name in NEW_READERS:
            module = run.load_module("layer_metrics", name)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, (cell["name"], name)
    # and this configuration's own, in a program without the layers
    _, cfg, traffic = run.resolve(manifest, tiny_sdar.CELL)
    context = {"cell": {"name": "no.such_cell"}, "config": cfg,
               "traffic": traffic}
    for name in NEW_READERS:
        if name != "bd_flash_tiles_walked_pct":     # a host counter: above
            module = run.load_module("layer_metrics", name)
            assert module.read({"steps": 10, "peaks": tiny.PEAKS}, {},
                               context) is None, name


# -- the arithmetic, against hand counts (ISSUE 33, "Sizing") ---------------

def test_parameters_by_part_are_the_hand_counts(published):
    cfg, _ = published
    a = costs.attention_params(cfg)
    assert a["q_proj"] == 2048 * 4096 == a["o_proj"]
    assert a["k_proj"] == a["v_proj"] == 2048 * 512
    assert sum(a.values()) - a["vectors"] == 18_874_368
    assert costs.expert_params(cfg) == 3 * 2048 * 768 == 4_718_592
    layer = costs.layer_params(cfg)
    assert layer["router"] == 262_144 and layer["routed"] == 75_497_472
    assert layer["attention"] + layer["vectors"] == 18_874_368 + 4_352
    assert sum(layer.values()) == 94_638_336
    total = 5 * 94_638_336 + 2 * 18_992 * 2048 + 2048
    assert costs.total_params(cfg) == total == 550_984_960
    assert abs(16 * total / 1e9 - 8.82) < 0.01                # GB of state
    # a whole layer's 128 experts under AdamW do not fit one chip twice
    assert 2 * 16 * 128 * costs.expert_params(cfg) / 1e9 > 16
    # and they are the reference's own shapes
    from benchmark.reference import sdar_moe as ref
    assert sum(int(np.prod(s)) for s in ref.param_shapes(cfg).values()) \
        == total


def test_flops_a_data_token_are_the_hand_counts(published):
    cfg, traffic = published
    seq = traffic["seq_len"]
    assert costs.allowed_pairs(seq, 4) == seq * seq + 4 * seq
    # by brute force at a small size: the mask's own count
    from benchmark.reference import sdar_moe as ref
    at = np.arange(2 * 64)
    assert int(np.asarray(ref.allowed(at, at, 64, 4)).sum()) \
        == costs.allowed_pairs(64, 4)
    per = costs.forward_flops_per_token(cfg, seq)
    assert per["projections"] == 5 * 2 * 2 * 18_874_368      # both copies
    assert per["scores"] == 5 * 2 * 32 * (seq + 4) * 2 * 128
    assert costs.slots_here_per_row(cfg) == 1.0
    assert per["moe"] == 5 * 2 * (2 * 262_144 + 2 * 4_718_592)
    assert per["head"] == 2 * 2048 * 18_992                   # noisy rows
    forward = sum(per.values())
    assert abs(forward / 1e9 - 1.226) < 0.001                 # Gflop a token
    shares = {k: round(100 * v / forward) for k, v in per.items()}
    assert shares == {"projections": 31, "scores": 55, "moe": 8, "head": 6}
    step = costs.train_flops_per_token(cfg, seq) * seq
    assert abs(step / 1e12 - 30.14) < 0.01                    # Tflop a step


def test_attention_kernel_costs_are_the_hand_counts(published):
    """32 heads over the allowed pairs of 2 x 8,192 rows: seven products
    of 2 x 128 flops a pair; Q-sized arrays 134.2 MB over both copies,
    K-sized 16.8 MB (4 key/value heads)."""
    cfg, traffic = published
    flops, nbytes = costs.attention_kernel_costs(cfg, traffic["seq_len"])
    assert flops == 7 * 2 * 32 * (8192 * 8192 + 4 * 8192) * 128
    assert abs(flops / 1e12 - 3.850) < 0.001
    q, k = 2 * 8192 * 32 * 128 * 2, 2 * 8192 * 4 * 128 * 2
    assert nbytes == 6 * q + 6 * k == 905_969_664
    from benchmark import kernel_costs
    share, bound = kernel_costs.roofline_share_pct(
        5 * flops, 5 * nbytes, 0.25, {"bf16_flops_per_s": 197e12,
                                      "hbm_bytes_per_s": 819e9})
    assert bound == "flops" and 39.0 < share < 39.2
    # the walked tiles hold more pairs than are allowed: 288 tiles of 512 x
    # 512 a head against L^2 + 4 L
    assert 288 * 512 * 512 / costs.allowed_pairs(8192, 4) > 1.12
