"""paddle_tpu.monitor.profile — HLO parse → per-op attribution, roofline
classification, fusion-menu ranking, ceilings, and the disabled-mode
zero-cost contract."""
import json
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import jit, monitor, nn, optimizer as opt
import paddle_tpu.nn.functional as F
from paddle_tpu.monitor import profile
from paddle_tpu.monitor.registry import read_jsonl


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """profile + monitor are process-global; every test starts dark."""
    for var in ("PADDLE_TPU_FLOPS_CEILING", "PADDLE_TPU_HBM_GBPS",
                "PADDLE_TPU_ROOFLINE_DEVICE", "PADDLE_TPU_PROFILE"):
        monkeypatch.delenv(var, raising=False)
    monitor.disable(flush_counters=False)
    monitor.reset()
    profile.disable()
    profile.reset()
    yield
    monitor.disable(flush_counters=False)
    monitor.reset()
    profile.disable()
    profile.reset()


# -- synthetic HLO for the parser units --------------------------------------

DOT_HLO = """\
HloModule test, is_scheduled=true

ENTRY %main.1 (a: f32[4,8], b: f32[8,16]) -> f32[4,16] {
  %a = f32[4,8]{1,0} parameter(0)
  %b = f32[8,16]{1,0} parameter(1)
  ROOT %dot.1 = f32[4,16]{1,0} dot(f32[4,8]{1,0} %a, f32[8,16]{1,0} %b), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/jit(main)/root/L0/dot_general"}
}
"""

FUSED_HLO = """\
HloModule test2, is_scheduled=true

%fused_computation (p0: f32[4,8]) -> f32[4,8] {
  %p0 = f32[4,8]{1,0} parameter(0)
  %exp.1 = f32[4,8]{1,0} exponential(f32[4,8]{1,0} %p0), metadata={op_name="jit(f)/jit(main)/root/F.softmax/exp"}
  ROOT %add.1 = f32[4,8]{1,0} add(f32[4,8]{1,0} %exp.1, f32[4,8]{1,0} %p0), metadata={op_name="jit(f)/jit(main)/root/transpose(jvp(F.softmax))/add"}
}

%region.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.2 = f32[] add(f32[] %x, f32[] %y)
}

ENTRY %main.2 (a: f32[4,8]) -> f32[4] {
  %a = f32[4,8]{1,0} parameter(0)
  %fus = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/jit(main)/root/F.softmax/add"}
  %c0 = f32[] constant(0)
  ROOT %reduce.1 = f32[4]{0} reduce(f32[4,8]{1,0} %fus, f32[] %c0), dimensions={1}, to_apply=%region.1, metadata={op_name="jit(f)/jit(main)/root/F.softmax/reduce_sum"}
}
"""


def test_parse_dot_flops_and_bytes():
    profile.register_scope("root", "root")
    profile.register_scope("L0", "layer")
    a = profile.attribute(DOT_HLO)
    assert a["total_flops"] == 2 * (4 * 16) * 8       # 2·out·K
    assert a["attributed_frac"] == 1.0
    (row,) = a["ops"]
    assert row["opcode"] == "dot"
    assert row["region"] == "L0"
    # operands (128 + 512) + output 256 bytes, f32
    assert row["bytes"] == 4 * (4 * 8 + 8 * 16 + 4 * 16)


def test_parse_fusion_reduce_transcendentals():
    profile.register_scope("root", "root")
    profile.register_scope("F.softmax", "functional")
    a = profile.attribute(FUSED_HLO)
    rows = {r["name"]: r for r in a["ops"]}
    # fusion = inner add (32 flops) + inner exp (32 transcendentals);
    # the transpose(jvp(...)) wrapper still resolves to F.softmax
    assert rows["fus"]["flops"] == 32
    assert rows["fus"]["transcendentals"] == 32
    assert rows["fus"]["region"] == "F.softmax"
    # reduce = in − out, its to_apply region body is folded, not counted
    assert rows["reduce.1"]["flops"] == 32 - 4
    assert a["total_flops"] == 32 + 28
    assert a["transcendentals"] == 32
    assert a["attributed_frac"] == 1.0


def test_unregistered_scopes_bucket_as_unattributed():
    # nothing registered: the root/L0 tokens mean nothing -> 0% attributed
    a = profile.attribute(DOT_HLO)
    assert a["attributed_frac"] == 0.0
    assert a["ops"][0]["region"] == profile.UNATTRIBUTED


def test_root_scope_never_attributes():
    # only the root is registered — everything under it must still
    # bucket as unattributed (the ≥90% bar must not be trivially true)
    profile.register_scope("root", "root")
    a = profile.attribute(DOT_HLO)
    assert a["attributed_frac"] == 0.0


# -- roofline ceilings --------------------------------------------------------

def test_roofline_ceilings_known_kind():
    c = profile.roofline_ceilings("TPU v5p")
    assert c["peak_flops"] == 459e12
    assert c["hbm_bytes_per_sec"] == 2765e9
    assert not c["assumed"]
    assert c["ridge_flops_per_byte"] == pytest.approx(459e12 / 2765e9)


def test_roofline_ceilings_unknown_kind_assumes_v5e():
    c = profile.roofline_ceilings("M2 Ultra")
    assert c["assumed"]
    assert "assumed" in c["device_kind"]
    assert c["peak_flops"] == 197e12
    assert c["hbm_bytes_per_sec"] == 819e9


def test_roofline_ceilings_env_overrides(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLOPS_CEILING", "2e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "100")
    c = profile.roofline_ceilings("whatever")
    assert c["peak_flops"] == 2e12
    assert c["hbm_bytes_per_sec"] == 100e9
    assert not c["assumed"]          # both ceilings pinned by the user


def test_roofline_device_env(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_ROOFLINE_DEVICE", "TPU v4")
    c = profile.roofline_ceilings()
    assert c["peak_flops"] == 275e12
    assert c["hbm_bytes_per_sec"] == 1228e9
    assert not c["assumed"]


def test_step_bandwidth_lookup_and_env(monkeypatch):
    from paddle_tpu.monitor import step as mstep
    assert mstep.ceilings_for_kind("TPU v5 lite")[1] == 819e9
    assert mstep.ceilings_for_kind("TPU v6e")[0] == 918e12
    assert mstep.ceilings_for_kind("cpu") == (None, None)
    monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "123")
    assert mstep.peak_hbm_bandwidth_for_device() == 123e9


# -- roofline classification boundaries ---------------------------------------

def test_classification_boundaries():
    ceil = {"peak_flops": 1.0, "hbm_bytes_per_sec": 1.0,
            "ridge_flops_per_byte": 1.0, "device_kind": "unit",
            "assumed": False}
    mk = lambda f, b: {"flops": float(f), "bytes": float(b),
                       "transcendentals": 0.0}
    above, below, at = profile._rooflined(
        [mk(100, 10), mk(10, 100), mk(50, 50)], ceil)
    assert above["bound"] == "compute" and above["headroom_s"] == 0.0
    assert below["bound"] == "memory"
    assert below["headroom_s"] == pytest.approx(100.0 - 10.0)
    assert below["mfu"] == pytest.approx(0.1)
    assert at["bound"] == "compute"   # exactly on the ridge: compute


def test_report_classifies_with_env_roofline(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FLOPS_CEILING", "1e9")
    monkeypatch.setenv("PADDLE_TPU_HBM_GBPS", "1")     # ridge = 1 F/B
    profile.register_scope("root", "root")
    profile.register_scope("L0", "layer")
    rep = profile.report(hlo=DOT_HLO)
    (row,) = rep["ops"]
    # dot: 1024 flops / 896 bytes -> AI > ridge -> compute-bound
    assert row["bound"] == "compute"
    assert row["arith_intensity"] == pytest.approx(1024 / 896)
    assert rep["hotspots"][0]["region"] == "L0"


# -- hlo_text truncation (satellite fix) --------------------------------------

def test_hlo_text_truncates_at_line_boundary(tmp_path):
    import jax
    import jax.numpy as jnp
    monitor.enable(str(tmp_path))
    fn = jax.jit(lambda x: jnp.tanh(x) @ x)
    monitor.xla.aot_capture(fn, "trunc", (np.eye(8, dtype="float32"),))
    full = monitor.xla.hlo_text("trunc", max_bytes=0) or \
        monitor.xla.executable("trunc").as_text()
    # big enough that whole lines fit under the limit (the first
    # HloModule header line alone is a few hundred bytes)
    cut = monitor.xla.hlo_text("trunc", max_bytes=len(full) // 2)
    assert cut is not None and cut != full
    body, tail = cut.rstrip("\n").rsplit("\n", 1)
    assert tail.startswith("... [truncated ") and tail.endswith(" bytes]")
    # every byte up to the marker is a prefix of whole lines
    assert full.startswith(body)
    assert full[len(body)] == "\n"
    dropped = int(tail.split("[truncated ")[1].split(" ")[0])
    assert dropped == len(full) - len(body)


# -- end-to-end: jitted MLP + Adam on CPU -------------------------------------

def _mlp_step(tmp_path, hidden=32):
    monitor.enable(str(tmp_path))
    profile.enable()
    model = nn.Sequential(nn.Linear(16, hidden), nn.ReLU(),
                          nn.Linear(hidden, 10))
    adam = opt.Adam(learning_rate=1e-3, parameters=model.parameters())

    @jit.to_static(models=[model], optimizers=[adam])
    def step(x, y):
        logits = model(x)
        loss = F.cross_entropy(logits, y)
        loss.backward()
        adam.step()
        return loss

    x = pt.to_tensor(np.random.RandomState(0).randn(8, 16)
                     .astype("float32"))
    y = pt.to_tensor(np.arange(8).astype("int64") % 10)
    step(x, y)
    return step


def test_mlp_adam_attribution_and_reconciliation(tmp_path):
    _mlp_step(tmp_path)
    rep = profile.report(top_k=8)
    assert rep is not None and rep["label"] == "jit.step"
    assert rep["label"] in monitor.xla.labels()
    # every flop lands in a named scope or the <unattributed> bucket,
    # and the parser's total agrees with XLA's own count within 1%
    assert rep["attributed_frac"] >= 0.90
    assert rep["flops_reconciliation"] == pytest.approx(1.0, abs=0.01)
    total = sum(o["flops"] for o in rep["ops"])
    assert total == pytest.approx(rep["total_flops"])
    regions = {r["region"] for r in rep["regions"]}
    # the SURVEY §2 fusion candidates surface from measurement
    assert "opt.Adam" in regions
    assert "F.cross_entropy" in regions
    assert any("Linear_0" in r for r in regions)
    for o in rep["ops"]:
        assert o["bound"] in ("compute", "memory")
        assert o["est_time_s"] >= 0
    # hotspot JSONL records landed in the sink
    recs = [r for r in read_jsonl(monitor.jsonl_path())
            if r.get("kind") == "hotspot"]
    assert recs and recs[0]["rank"] == 1
    assert {r["region"] for r in recs} <= regions
    # /snapshot surfaces the evidence pointers
    snap = monitor.export.snapshot_payload()
    assert snap["xla_cost"]["last_label"] == "jit.step"
    assert "jit.step" in snap["xla_cost"]["labels"]
    assert snap["hotspots"]["attributed_frac"] >= 0.90
    assert snap["hotspots"]["hotspots"][0]["rank"] == 1


def test_ranking_stable_across_reports(tmp_path):
    _mlp_step(tmp_path)
    r1 = profile.report(top_k=10)
    r2 = profile.report(top_k=10)
    order1 = [(h["rank"], h["region"]) for h in r1["hotspots"]]
    order2 = [(h["rank"], h["region"]) for h in r2["hotspots"]]
    assert order1 == order2
    assert [h["rank"] for h in r1["hotspots"]] == \
        list(range(1, len(order1) + 1))
    # headroom is monotonically non-increasing down the menu
    heads = [h["headroom_s"] for h in r1["hotspots"]]
    assert heads == sorted(heads, reverse=True)


def test_layer_scope_names_stable_per_instance(tmp_path):
    profile.enable()
    l0, l1 = nn.Linear(4, 4), nn.Linear(4, 4)
    x = pt.to_tensor(np.zeros((2, 4), dtype="float32"))
    l0(x), l1(x), l0(x)
    assert l0._profile_scope == "Linear_0"
    assert l1._profile_scope == "Linear_1"
    scopes = profile.scopes()
    assert scopes["Linear_0"] == "layer" and scopes["Linear_1"] == "layer"
    # a reset keeps instance names on re-entry instead of renumbering
    profile.reset()
    l0(x)
    assert l0._profile_scope == "Linear_0"
    assert profile.scopes()["Linear_0"] == "layer"


def test_format_table_renders(tmp_path):
    _mlp_step(tmp_path)
    rep = profile.report()
    table = profile.format_table(rep)
    assert "opt.Adam" in table and "region" in table
    assert "attributed" in table
    assert profile.format_table(None).startswith("profile: no captured")


def test_flight_record_bundles_op_ledger(tmp_path):
    _mlp_step(tmp_path)
    profile.report()
    d = monitor.trace.flight_record("test", directory=str(tmp_path / "fl"))
    assert d is not None
    ledger = json.load(open(f"{d}/op_ledger.json"))
    assert ledger["label"] == "jit.step"
    assert float(ledger["attributed_frac"]) >= 0.90


# -- disabled mode: one flag check, nothing else ------------------------------

def _bombs(monkeypatch):
    bomb = lambda *a, **k: (_ for _ in ()).throw(
        AssertionError("profiling touched while disabled"))
    for name in ("layer_scope", "fscope", "optimizer_scope",
                 "backward_scope", "armed", "parse_hlo"):
        monkeypatch.setattr(profile, name, bomb)


def test_disabled_mode_eager_enters_no_scope_and_parses_nothing(monkeypatch):
    """Eager dispatch with the profile off: every labelling site is the
    one check of ``profile.live`` and nothing behind it runs."""
    assert profile.scopes_on is False and profile.live is False
    _bombs(monkeypatch)
    model = nn.Sequential(nn.Linear(4, 4), nn.ReLU(), nn.LayerNorm(4))
    adam = opt.Adam(learning_rate=1e-3, parameters=model.parameters())
    x = pt.to_tensor(np.ones((2, 4), dtype="float32"))
    y = pt.to_tensor(np.zeros((2,), dtype="int64"))
    for _ in range(2):   # labels, forward, backward, update: no bomb trips
        loss = F.cross_entropy(F.softmax(model(x)), y)
        loss.backward()
        adam.step()
        adam.clear_grad()
    assert profile.last_report() is None
    assert profile.scopes() == {}


def _bert_tiny_step(seen=None):
    """A BERT-tiny pre-training step through jit.to_static, as the
    benchmark builds it, with NOTHING armed by the user."""
    from paddle_tpu import amp
    from paddle_tpu.models.bert import BertConfig, BertForPretraining
    cfg = BertConfig(vocab_size=512, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=2, intermediate_size=128,
                     max_position_embeddings=64, hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    model = BertForPretraining(cfg)
    adamw = opt.AdamW(learning_rate=1e-4, parameters=model.parameters())

    def bert_step(ids, types, mlm, nsp):
        if seen is not None:
            # at trace time, what ANOTHER thread sees of the labelling
            t = threading.Thread(target=lambda: seen.append(
                (profile.scopes_on, profile.armed())))
            t.start()
            t.join()
            seen.append((profile.scopes_on, profile.armed()))
        with amp.auto_cast(dtype="bfloat16"):
            logits, nsp_logits = model(ids, types)
        loss = model.loss(logits.astype("float32"),
                          nsp_logits.astype("float32"), mlm, nsp)
        loss.backward()
        adamw.step()
        adamw.clear_grad()
        return loss

    step = jit.to_static(bert_step, models=[model], optimizers=[adamw])
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 512, (4, 16), dtype=np.int32)
    mlm = np.where(rng.random((4, 16)) < 0.15,
                   rng.integers(0, 512, (4, 16)), -1).astype(np.int32)
    feed = [pt.to_tensor(a) for a in (
        ids, np.zeros((4, 16), np.int32), mlm,
        rng.integers(0, 2, (4,), dtype=np.int32))]
    return step, feed


def test_compiled_step_carries_its_scopes_with_nothing_armed(tmp_path):
    monitor.enable(str(tmp_path))        # keeps the executable; no profile
    seen = []
    step, feed = _bert_tiny_step(seen)
    assert np.isfinite(float(step(*feed).numpy()))
    # the trace armed its own thread and no other, and put things back
    assert seen == [(False, False), (False, True)]
    assert profile.scopes_on is False and profile.live is False
    assert profile.armed() is False

    rows = profile.instruction_ledger()
    assert rows and {r["module"] for r in rows} == {"jit_bert_step"}
    assert {r["label"] for r in rows} == {"jit.bert_step"}
    flops = {ph: 0.0 for ph in profile.PHASES}
    named = total = 0.0
    regions = {ph: set() for ph in profile.PHASES}
    for r in rows:
        for part in r["parts"]:
            flops[part["phase"]] += part["flops"]
            regions[part["phase"]].add(part["region"])
            total += part["flops"]
            if part["phase"] != "none" \
                    and part["region"] != profile.UNATTRIBUTED:
                named += part["flops"]
    assert named / total >= 0.90
    assert flops["none"] / total < 0.05
    # backward ops under bwd with their layer's region, the update under opt
    assert flops["bwd"] > flops["fwd"] > 0 and flops["opt"] > 0
    assert regions["opt"] == {"opt.AdamW"}
    assert any(r.endswith("/Linear_0") for r in regions["bwd"])
    assert any("LayerNorm" in r and r.endswith("F.layer_norm")
               for r in regions["bwd"])
    assert not any(r.startswith("opt.") for r in regions["bwd"] | regions["fwd"])
    # eager code after the step is dark again
    scopes_before = profile.scopes()
    nn.Linear(4, 4)(pt.to_tensor(np.ones((2, 4), dtype="float32")))
    assert profile.scopes() == scopes_before


def test_phase_and_region_reads_an_op_name():
    scope_map = {"step": "root", "bwd": "phase", "Linear_0": "layer",
                 "F.softmax": "functional", "opt.Adam": "optimizer",
                 "arena.pack": "op"}
    phase_map = {"bwd": "bwd", "opt.Adam": "opt", "arena.pack": "opt"}
    read = lambda name: profile.phase_and_region(name, scope_map, phase_map)
    assert read("jit(step)/step/Linear_0/dot_general") == ("fwd", "Linear_0")
    assert read("jit(step)/step/bwd/Linear_0/F.softmax/transpose(jvp())/mul") \
        == ("bwd", "Linear_0/F.softmax")
    assert read("jit(step)/step/opt.Adam/sub") == ("opt", "opt.Adam")
    assert read("jit(step)/step/arena.pack/concatenate") == \
        ("opt", "arena.pack")
    # loss scaling and gradient clipping sit under the root and nothing
    # else: forward by the rule, unattributed by region
    assert read("jit(step)/step/mul") == ("fwd", profile.UNATTRIBUTED)
    # no root scope: the compiler's own, an argument's copy, eager code
    assert read("") == ("none", profile.UNATTRIBUTED)
    assert read("state_vals[397]") == ("none", profile.UNATTRIBUTED)
    assert read("jit(other)/Linear_0/add") == ("none", "Linear_0")


TWO_PHASE_HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (p0: bf16[512,256], p1: bf16[512,128], p2: f32[256,128], p3: f32[256,128]) -> (f32[256,128], f32[256,128]) {
  %p0 = bf16[512,256]{1,0} parameter(0)
  %p1 = bf16[512,128]{1,0} parameter(1)
  %p2 = f32[256,128]{1,0} parameter(2)
  %p3 = f32[256,128]{1,0} parameter(3)
  %dot.1 = f32[256,128]{1,0} dot(bf16[512,256]{1,0} %p0, bf16[512,128]{1,0} %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/step/bwd/Linear_0/transpose(jvp())/dot_general"}
  %c = f32[] constant(0.1)
  %b = f32[256,128]{1,0} broadcast(f32[] %c), dimensions={}
  %mul.1 = f32[256,128]{1,0} multiply(f32[256,128]{1,0} %dot.1, f32[256,128]{1,0} %b), metadata={op_name="jit(step)/step/opt.SGD/mul"}
  %add.1 = f32[256,128]{1,0} add(f32[256,128]{1,0} %p3, f32[256,128]{1,0} %mul.1), metadata={op_name="jit(step)/step/opt.SGD/add"}
  %sub.1 = f32[256,128]{1,0} subtract(f32[256,128]{1,0} %p2, f32[256,128]{1,0} %add.1), metadata={op_name="jit(step)/step/opt.SGD/sub"}
  ROOT %t = (f32[256,128]{1,0}, f32[256,128]{1,0}) tuple(f32[256,128]{1,0} %sub.1, f32[256,128]{1,0} %add.1)
}

ENTRY %main.9 (w: f32[256,128], v: f32[256,128], x: bf16[512,256], g: bf16[512,128]) -> (f32[256,128], f32[256,128]) {
  %w = f32[256,128]{1,0} parameter(0), metadata={op_name="state_vals[0]"}
  %v = f32[256,128]{1,0} parameter(1)
  %x = bf16[512,256]{1,0} parameter(2)
  %g = bf16[512,128]{1,0} parameter(3)
  %copy-start.1 = (f32[256,128]{1,0}, f32[256,128]{1,0}, u32[]) copy-start(f32[256,128]{1,0} %w)
  %copy-done.1 = f32[256,128]{1,0} copy-done((f32[256,128]{1,0}, f32[256,128]{1,0}, u32[]) %copy-start.1)
  %ln = (f32[512,128]{1,0}, f32[512,1]{1,0}) custom-call(bf16[512,128]{1,0} %g), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/step/bwd/LayerNorm_0/F.layer_norm/transpose(jvp(layer_norm_bwd))/pallas_call"}
  ROOT %multiply_subtract_fusion.3 = (f32[256,128]{1,0}, f32[256,128]{1,0}) fusion(bf16[512,256]{1,0} %x, bf16[512,128]{1,0} %g, f32[256,128]{1,0} %copy-done.1, f32[256,128]{1,0} %v), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/step/opt.SGD/sub"}
}
"""
_TWO_PHASE_SCOPES = {"step": "root", "bwd": "phase", "Linear_0": "layer",
                     "LayerNorm_0": "layer", "F.layer_norm": "functional",
                     "opt.SGD": "optimizer"}
_TWO_PHASE_PHASES = {"bwd": "bwd", "opt.SGD": "opt"}


def test_instruction_ledger_splits_a_fusion_that_holds_two_phases():
    rows = profile.instruction_ledger(
        label="fixture", hlo=TWO_PHASE_HLO, scope_map=_TWO_PHASE_SCOPES,
        phase_map=_TWO_PHASE_PHASES)
    by_name = {r["name"]: r for r in rows}
    assert set(by_name) == {"copy-start.1", "copy-done.1", "ln",
                            "multiply_subtract_fusion.3"}
    assert {r["module"] for r in rows} == {"jit_step"}
    fusion = by_name["multiply_subtract_fusion.3"]
    assert fusion["opcode"] == "fusion" and fusion["kernel"] is None
    parts = {(p["phase"], p["region"]): p for p in fusion["parts"]}
    assert set(parts) == {("bwd", "Linear_0"), ("opt", "opt.SGD")}
    # the weight-gradient matmul and the three elementwise ops of the update
    assert parts[("bwd", "Linear_0")]["flops"] == 2 * 256 * 128 * 512
    assert parts[("opt", "opt.SGD")]["flops"] == 3 * 256 * 128
    # bytes are what crosses the fusion's boundary, charged to the
    # instruction that reads or makes them: both bf16 operands to the
    # matmul; w, v and the two results to the update
    assert parts[("bwd", "Linear_0")]["bytes"] == 2 * (512 * 256 + 512 * 128)
    assert parts[("opt", "opt.SGD")]["bytes"] == 4 * 4 * 256 * 128
    # a Pallas kernel is known by its name=
    assert by_name["ln"]["kernel"] == "layer_norm_bwd"
    assert [(p["phase"], p["region"]) for p in by_name["ln"]["parts"]] == \
        [("bwd", "LayerNorm_0/F.layer_norm")]
    # what the compiler made to move data takes the labels of what it serves
    for name in ("copy-start.1", "copy-done.1"):
        assert by_name[name]["serves"] == "multiply_subtract_fusion.3"
        assert [(p["phase"], p["region"]) for p in by_name[name]["parts"]] \
            == [("opt", "opt.SGD")]
    assert fusion["serves"] is None


LOOP_PREFETCH_HLO = """\
HloModule jit_step, is_scheduled=true

%body (p: (s32[], f32[64,8])) -> (s32[], f32[64,8]) {
  %p = (s32[], f32[64,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[64,8]{1,0}) %p), index=0
  %x = f32[64,8]{1,0} get-tuple-element((s32[], f32[64,8]{1,0}) %p), index=1
  %slice-start.1 = ((f32[64,8]{1,0}), f32[64,8]{1,0:S(1)}, s32[]) slice-start(f32[64,8]{1,0} %x), slice={[0:64], [0:8]}
  %slice-done.1 = f32[64,8]{1,0:S(1)} slice-done(((f32[64,8]{1,0}), f32[64,8]{1,0:S(1)}, s32[]) %slice-start.1)
  %one = s32[] constant(1)
  %next = s32[] add(s32[] %i, s32[] %one), metadata={op_name="jit(step)/step/bwd/Linear_0/while/body/add"}
  ROOT %t = (s32[], f32[64,8]{1,0}) tuple(s32[] %next, f32[64,8]{1,0:S(1)} %slice-done.1)
}

%cond (p: (s32[], f32[64,8])) -> pred[] {
  %p = (s32[], f32[64,8]{1,0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[64,8]{1,0}) %p), index=0
  %n = s32[] constant(8)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT, metadata={op_name="jit(step)/step/bwd/Linear_0/while/cond/lt"}
}

ENTRY %main.3 (x: f32[64,8]) -> (s32[], f32[64,8]) {
  %x = f32[64,8]{1,0} parameter(0)
  %zero = s32[] constant(0)
  %init = (s32[], f32[64,8]{1,0}) tuple(s32[] %zero, f32[64,8]{1,0} %x)
  ROOT %while.1 = (s32[], f32[64,8]{1,0}) while((s32[], f32[64,8]{1,0}) %init), condition=%cond, body=%body, metadata={op_name="jit(step)/step/bwd/Linear_0/while"}
}
"""


def test_a_loop_bodys_own_prefetch_takes_the_labels_of_its_loop():
    """A prefetch that the compiler carries from one iteration to the
    next is read by the body's ROOT tuple alone: no labelled neighbour in
    its computation. It serves the loop, and is not phase ``none``."""
    rows = profile.instruction_ledger(
        label="fixture", hlo=LOOP_PREFETCH_HLO, scope_map=_TWO_PHASE_SCOPES,
        phase_map=_TWO_PHASE_PHASES)
    by_name = {r["name"]: r for r in rows}
    for name in ("slice-start.1", "slice-done.1"):
        assert by_name[name]["serves"] == "while.1"
        assert [(p["phase"], p["region"]) for p in by_name[name]["parts"]] \
            == [("bwd", "Linear_0")]
    assert by_name["next"]["serves"] is None


def test_instruction_ledger_is_empty_without_a_captured_executable():
    assert profile.instruction_ledger() == []


def test_conv_flops_count_live_window_positions_only():
    """On TPU a batched matmul is a convolution whose batch dimensions sit
    in a dilated window with one live position per output."""
    batched = """\
HloModule m

ENTRY %main (a: bf16[64,12,128,64], b: bf16[64,12,128,64]) -> f32[64,12,128,128] {
  %a = bf16[64,12,128,64]{3,2,1,0} parameter(0)
  %b = bf16[64,12,128,64]{3,2,1,0} parameter(1)
  ROOT %c = f32[64,12,128,128]{3,2,1,0} convolution(bf16[64,12,128,64]{3,2,1,0} %a, bf16[64,12,128,64]{3,2,1,0} %b), window={size=64x12 stride=63x11 lhs_dilate=64x12}, dim_labels=01bf_01oi->01bf, metadata={op_name="jit(f)/root/L0/dot_general"}
}
"""
    rep = profile.attribute(batched, scope_map={"root": "root", "L0": "layer"})
    assert rep["total_flops"] == 2 * 64 * 12 * 128 * 128 * 64
    plain = """\
HloModule m

ENTRY %main (x: f32[8,3,32,32], k: f32[16,3,3,3]) -> f32[8,16,16,16] {
  %x = f32[8,3,32,32]{3,2,1,0} parameter(0)
  %k = f32[16,3,3,3]{3,2,1,0} parameter(1)
  ROOT %c = f32[8,16,16,16]{3,2,1,0} convolution(f32[8,3,32,32]{3,2,1,0} %x, f32[16,3,3,3]{3,2,1,0} %k), window={size=3x3 stride=2x2 pad=1_1x1_1}, dim_labels=bf01_oi01->bf01, metadata={op_name="jit(f)/root/L0/conv"}
}
"""
    rep = profile.attribute(plain, scope_map={"root": "root", "L0": "layer"})
    # 16 outputs a side: the first hangs one tap into the low padding
    live = 16 * 3 - 1
    assert rep["total_flops"] == 2 * 8 * 16 * 3 * live * live


def test_enable_env_var(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PROFILE", "1")
    monitor.enable(str(tmp_path))
    assert profile.scopes_on is True
