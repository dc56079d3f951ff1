"""`correct` has to be able to come out false: the float8 control put in
the program's place, and the timed path broken underneath a run that the
harness otherwise drives whole. CPU, at a size a test run can hold; the
control at the cells' own sizes ran on the chip (PERF.md section 2)."""
import math

import pytest

from benchmark import run
from benchmark.tests import tiny


@pytest.fixture()
def job(monkeypatch):
    module = run.load_module("jobs", "train_loop")
    monkeypatch.setattr(module, "device_peak_bytes", lambda: 5_000_000_000)
    return module


def _quiet(*a, **k):
    pass


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_003])
def test_the_float8_control_is_not_correct_and_the_reference_is(job, seed):
    cell, cfg, traffic, limits = tiny.bert(hidden=128, layers=2, heads=2,
                                           rows=16, seq=64)
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
    assert all("limit" in f and "value" in f for f in lines)
    assert job.compare(want, want, limits, leaves, _quiet) is True


def test_an_unknown_precision_is_refused():
    from benchmark.reference.common import bilinear
    with pytest.raises(ValueError):
        bilinear(lambda a, b: a @ b, "int4")


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        job, monkeypatch):
    from paddle_tpu import optimizer
    monkeypatch.setattr(optimizer.AdamW, "step", lambda self: None)
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny.bert()
    lines = []
    monkeypatch.setattr(run, "say", lambda phase, **f: lines.append(f))
    result = run.run_cell(manifest, cell, cfg, traffic,
                          tiny.roomy(limits), 7, 0.3, False,
                          tiny.CPU, tiny.PEAKS)
    assert result["correct"] is False
    gaps = {f["number"]: float(f["value"]) for f in lines if "number" in f}
    # nothing moved and no gradient reached the optimizer: every leaf's
    # norm is 0 against the reference's (leaves under the median leaf's
    # norm are measured against that, so the median gap is under 1)
    for kind in ("delta_norm_gap", "first_grad_norm_gap"):
        assert gaps[kind + "_worst"] == pytest.approx(1.0), kind
        assert gaps[kind + "_median"] > 0.5, kind
    assert result["failed"] == 0 and result["attempted"] >= 2


def test_a_part_of_the_batch_left_out_is_not_correct(job, monkeypatch):
    family = run.load_module("families", "bert_pretrain")
    build = family.build

    def build_dropping_rows(cfg, traffic, weights):
        trainer = build(cfg, traffic, weights)
        whole = trainer.step

        def step(ids, types, mlm, nsp):
            import paddle_tpu as pt
            labels = mlm.numpy().copy()
            labels[len(labels) // 2:] = -1      # half the rows never scored
            return whole(ids, types, pt.to_tensor(labels), nsp)

        trainer.step = step
        return trainer

    monkeypatch.setattr(family, "build", build_dropping_rows)
    manifest = tiny.manifest()
    cell, cfg, traffic, limits = tiny.bert(rows=16, seq=32)
    lines = []
    monkeypatch.setattr(run, "say", lambda phase, **f: lines.append(f))
    result = run.run_cell(manifest, cell, cfg, traffic,
                          tiny.roomy(limits), 9, 0.3, False,
                          tiny.CPU, tiny.PEAKS)
    assert result["correct"] is False
    first = next(f for f in lines if f.get("number") == "loss_gap_step1")
    assert first["holds"] is False and math.isfinite(float(first["value"]))


def test_a_step_that_raises_counts_as_failed_and_not_correct(job, monkeypatch):
    family = run.load_module("families", "bert_pretrain")
    build = family.build

    def build_raising(cfg, traffic, weights):
        trainer = build(cfg, traffic, weights)
        whole, calls = trainer.step, []

        def step(*feed):
            calls.append(1)
            # past the checked and the warm steps: inside the window
            if len(calls) == job.CHECKED_STEPS + job.WARM_STEPS + 4:
                raise RuntimeError("device lost")
            return whole(*feed)

        trainer.step = step
        return trainer

    monkeypatch.setattr(family, "build", build_raising)
    cell, cfg, traffic, limits = tiny.bert()
    result = run.run_cell(tiny.manifest(), cell, cfg, traffic,
                          tiny.roomy(limits), 5, 0.5, False,
                          tiny.CPU, tiny.PEAKS)
    assert result["correct"] is False
    assert result["failed"] == 1
