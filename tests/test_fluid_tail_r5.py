"""Round-5 fluid namespace tail: dygraph decay classes, legacy RNN
cells, dataset/train_from_dataset, fluid.save/load, flags, and the
small utility modules (reference: the corresponding fluid/*.py)."""
import math
import os

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import fluid, nn, optimizer, static
from paddle_tpu.fluid import dygraph


# ---- dygraph decay classes -------------------------------------------------

def test_cosine_decay_epoch_granular():
    d = dygraph.CosineDecay(0.1, step_each_epoch=10, epochs=4)
    first = [d() for _ in range(10)]
    # whole first epoch stays at base lr (cur_epoch = 0)
    assert all(v == pytest.approx(0.1) for v in first)
    v = d()  # epoch 1
    assert v == pytest.approx(0.1 * 0.5 * (math.cos(math.pi / 4) + 1))


def test_piecewise_natural_exp_inverse_time():
    p = dygraph.PiecewiseDecay([2, 4], [1.0, 0.5, 0.1], begin=0)
    assert [p() for _ in range(5)] == [1.0, 1.0, 0.5, 0.5, 0.1]

    n = dygraph.NaturalExpDecay(1.0, decay_steps=2, decay_rate=0.5,
                                staircase=True)
    n()  # step 0
    assert n() == pytest.approx(1.0)           # floor(1/2)=0
    assert n() == pytest.approx(math.exp(-0.5))  # floor(2/2)=1

    it = dygraph.InverseTimeDecay(1.0, decay_steps=1, decay_rate=1.0)
    assert it() == pytest.approx(1.0)
    assert it() == pytest.approx(0.5)
    assert it() == pytest.approx(1 / 3)


def test_polynomial_exponential_noam_warmup():
    pd = dygraph.PolynomialDecay(1.0, decay_steps=10,
                                 end_learning_rate=0.0, power=1.0)
    assert pd() == pytest.approx(1.0)
    assert pd() == pytest.approx(0.9)

    e = dygraph.ExponentialDecay(1.0, decay_steps=1, decay_rate=0.5)
    assert e() == pytest.approx(1.0)
    assert e() == pytest.approx(0.5)
    assert e() == pytest.approx(0.25)

    nd = dygraph.NoamDecay(d_model=64, warmup_steps=4)
    vals = [nd() for _ in range(8)]
    assert np.argmax(vals) == 3  # peak at warmup boundary
    assert vals[3] == pytest.approx((64 ** -0.5) * (4 ** -0.5))

    w = dygraph.LinearLrWarmup(0.1, warmup_steps=5, start_lr=0.0,
                               end_lr=0.1)
    ramp = [w() for _ in range(4)]  # begin=1: steps 1..4
    np.testing.assert_allclose(ramp, [0.02, 0.04, 0.06, 0.08],
                               rtol=1e-6)
    assert w() == pytest.approx(0.1)  # step 5 >= warmup
    with pytest.raises(AssertionError):
        dygraph.LinearLrWarmup(0.1, 5, start_lr=1.0, end_lr=0.1)
    with pytest.raises(TypeError):
        dygraph.LinearLrWarmup("lr", 5, 0.0, 0.1)


def test_decay_drives_optimizer_per_step():
    """The optimizer advances the 1.x decay on each step() (reference
    dygraph minimize path), and checkpoints carry step_num."""
    w = pt.Parameter(np.zeros((1,), "f4"))
    decay = dygraph.PiecewiseDecay([1, 2], [1.0, 0.1, 0.01], begin=0)
    o = optimizer.SGD(learning_rate=decay, parameters=[w])
    for _ in range(3):
        (w * 1.0).sum().backward()  # grad = 1
        o.step()
        o.clear_grad()
    # steps applied lrs 1.0, 0.1, 0.01
    np.testing.assert_allclose(w.numpy(), [-1.11], rtol=1e-5)
    state = o.state_dict()
    assert state["__lr_decay__"]["step_num"] == 3
    o2 = optimizer.SGD(
        learning_rate=dygraph.PiecewiseDecay([1, 2], [1.0, 0.1, 0.01],
                                             begin=0),
        parameters=[w])
    o2.set_state_dict(state)
    assert o2._lr_decay.step_num == 3


# ---- legacy dygraph RNN cells ----------------------------------------------

def test_dygraph_lstm_cell_both_impls():
    pt.seed(0)
    for cudnn in (True, False):
        cell = dygraph.LSTMCell(8, 4, use_cudnn_impl=cudnn)
        x = pt.to_tensor(np.random.randn(2, 4).astype("f4"))
        h = pt.to_tensor(np.zeros((2, 8), "f4"))
        c = pt.to_tensor(np.zeros((2, 8), "f4"))
        nh, nc = cell(x, h, c)
        assert tuple(nh.shape) == (2, 8) and tuple(nc.shape) == (2, 8)
        nh.sum().backward()
        grads = [p.grad for p in cell.parameters() if p.grad is not None]
        assert grads and all(np.isfinite(np.asarray(g)).all()
                             for g in grads)


def test_dygraph_gru_cell_both_impls():
    pt.seed(1)
    for cudnn in (True, False):
        cell = dygraph.GRUCell(8, 4, use_cudnn_impl=cudnn)
        x = pt.to_tensor(np.random.randn(2, 4).astype("f4"))
        h = pt.to_tensor(np.zeros((2, 8), "f4"))
        nh = cell(x, h)
        assert tuple(nh.shape) == (2, 8)
        nh.sum().backward()
        grads = [p.grad for p in cell.parameters() if p.grad is not None]
        assert grads and all(np.isfinite(np.asarray(g)).all()
                             for g in grads)


def test_declarative_decorator():
    lin = nn.Linear(4, 2)

    @dygraph.declarative
    def f(x):
        return lin(x) * 2.0

    x = pt.to_tensor(np.ones((3, 4), "f4"))
    out = f(x)
    ref = (lin(x) * 2.0).numpy()
    np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5)
    assert callable(dygraph.dygraph_to_static_func(lambda x: x))


# ---- fluid.dataset + train_from_dataset ------------------------------------

def _write_multislot(path, n=16):
    rng = np.random.RandomState(0)
    with open(path, "w") as fh:
        for _ in range(n):
            x = rng.rand(2)
            y = [x[0] * 2 + x[1]]
            fh.write(f"2 {x[0]:.4f} {x[1]:.4f} 1 {y[0]:.4f}\n")


def test_inmemory_dataset_batches(tmp_path):
    f = tmp_path / "a.txt"
    _write_multislot(str(f), n=10)
    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(4)
    ds.set_filelist([str(f)])

    class V:
        def __init__(self, name):
            self.name = name
    ds.set_use_var([V("x"), V("y")])
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 10
    ds.local_shuffle()
    batches = list(ds._batches())
    assert [b["x"].shape for b in batches] == [(4, 2), (4, 2), (2, 2)]
    assert batches[0]["y"].shape == (4, 1)


def test_preload_into_memory_matches_serial_load(tmp_path):
    """preload_into_memory(thread_num) + wait_preload_done must produce
    the exact record store load_into_memory builds — same count, same
    order, same batch contents — on both the native-columnar and the
    python-record parse paths."""
    files = []
    for i in range(4):
        f = tmp_path / f"p{i}.txt"
        _write_multislot(str(f), n=6)
        files.append(str(f))

    class V:
        def __init__(self, name):
            self.name = name

    def make(native=True):
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_batch_size(5)
        ds.set_filelist(files)
        ds.set_use_var([V("x"), V("y")])
        ds.use_native_parse = native
        return ds

    for native in (True, False):
        a = make(native)
        a.load_into_memory()
        b = make(native)
        b.preload_into_memory(thread_num=4)
        b.wait_preload_done()
        assert a.get_memory_data_size() == b.get_memory_data_size() == 24
        for ba, bb in zip(a._batches(), b._batches()):
            for k in ba:
                np.testing.assert_array_equal(ba[k], bb[k])
    # wait without a preload in flight is a no-op, and double-wait is safe
    b.wait_preload_done()


def test_preload_into_memory_thread_scaling(tmp_path):
    """4 preload threads must cut wall-clock >= 2x over 1 thread. The
    per-file cost is pinned in the pipe command (a GIL-releasing
    subprocess wait), so the bound is deterministic on any host — the
    only way to beat the serial floor is genuinely concurrent file
    loads."""
    import time
    files = []
    for i in range(8):
        f = tmp_path / f"s{i}.txt"
        _write_multislot(str(f), n=4)
        files.append(str(f))

    class V:
        def __init__(self, name):
            self.name = name

    def run(threads):
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_batch_size(4)
        ds.set_filelist(files)
        ds.set_use_var([V("x"), V("y")])
        ds.set_pipe_command("sleep 0.2; cat")
        t0 = time.perf_counter()
        ds.preload_into_memory(thread_num=threads)
        ds.wait_preload_done()
        elapsed = time.perf_counter() - t0
        assert ds.get_memory_data_size() == 32
        return elapsed

    serial = run(1)     # >= 8 * 0.2s by construction
    parallel = run(4)   # ideal ~2 waves of 0.2s
    assert serial / parallel >= 2.0, (serial, parallel)


def test_queue_dataset_shuffle_raises(tmp_path):
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    with pytest.raises(NotImplementedError):
        ds.local_shuffle()
    with pytest.raises(ValueError):
        fluid.DatasetFactory().create_dataset("NoSuchDataset")


def test_train_from_dataset(tmp_path):
    f = tmp_path / "train.txt"
    _write_multislot(str(f), n=32)
    pt.enable_static()
    try:
        prog = static.Program()
        startup = static.Program()
        with static.program_guard(prog, startup):
            x = static.data("x", [None, 2], "float32")
            y = static.data("y", [None, 1], "float32")
            pred = fluid.layers.fc(x, size=1)
            loss = fluid.layers.reduce_mean(
                fluid.layers.square(pred - y))
            optimizer.SGD(learning_rate=0.1).minimize(loss)
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_batch_size(8)
        ds.set_filelist([str(f)])
        ds.set_use_var([x, y])
        ds.load_into_memory()
        exe = static.Executor()
        exe.run(startup)
        losses = []
        for _ in range(20):
            exe.train_from_dataset(prog, ds, fetch_list=[loss])
            out, = exe.run(prog, feed={"x": np.zeros((1, 2), "f4"),
                                       "y": np.zeros((1, 1), "f4")},
                           fetch_list=[loss])
            losses.append(float(out))
        assert losses[-1] < losses[0]
    finally:
        pt.disable_static()


# ---- fluid.save / fluid.load ------------------------------------------------

def test_fluid_save_load_roundtrip(tmp_path):
    pt.enable_static()
    try:
        prog = static.Program()
        startup = static.Program()
        with static.program_guard(prog, startup):
            x = static.data("x", [None, 3], "float32")
            yv = fluid.layers.fc(x, size=2)
            loss = fluid.layers.reduce_mean(yv)
            optimizer.Adam(learning_rate=0.01).minimize(loss)
        exe = static.Executor()
        exe.run(startup)
        feed = {"x": np.ones((4, 3), "f4")}
        exe.run(prog, feed=feed, fetch_list=[loss])
        before = {n: v.numpy().copy()
                  for n, v in prog.param_vars.items()}
        opt_before = prog.optimizers[0][0].state_dict()
        fluid.save(prog, str(tmp_path / "model"))
        assert (tmp_path / "model.pdparams").exists()  # exact suffix
        assert (tmp_path / "model.pdopt").exists()  # Adam has slots
        # perturb then restore through the same prefix save used
        for v in prog.param_vars.values():
            v.set_value(np.zeros_like(v.numpy()))
        fluid.load(prog, str(tmp_path / "model"))
        for n, v in prog.param_vars.items():
            np.testing.assert_allclose(v.numpy(), before[n])
        # optimizer slot state restored too (moment slots roundtrip)
        opt_after = prog.optimizers[0][0].state_dict()
        restored = {k: v for k, v in opt_after.items()
                    if hasattr(v, "numpy")}
        assert restored  # Adam created moment slots
        for k, v in restored.items():
            np.testing.assert_allclose(
                np.asarray(v.numpy()),
                np.asarray(opt_before[k].numpy()))
        with pytest.raises(ValueError):
            fluid.save(prog, str(tmp_path) + "/")
    finally:
        pt.disable_static()


# ---- flags / misc utility modules ------------------------------------------

def test_set_get_flags():
    fluid.set_flags({"FLAGS_eager_delete_tensor_gb": 1.5})
    assert fluid.get_flags("FLAGS_eager_delete_tensor_gb") == {
        "FLAGS_eager_delete_tensor_gb": 1.5}
    out = fluid.get_flags(["FLAGS_eager_delete_tensor_gb",
                           "FLAGS_use_mkldnn"])
    assert out["FLAGS_use_mkldnn"] is False
    with pytest.raises(TypeError):
        fluid.set_flags(["FLAGS_use_mkldnn"])
    with pytest.raises(TypeError):
        fluid.get_flags(3)
    with pytest.raises(ValueError):
        fluid.get_flags("FLAGS_never_heard_of_it")
    with pytest.raises(RuntimeError):
        fluid.framework.load_op_library("libcustom.so")
    with pytest.raises(RuntimeError):
        with fluid.profiler.cuda_profiler("out.txt"):
            pass


def test_lod_tensor_constructors():
    t = fluid.create_lod_tensor(np.ones((5, 3), "f4"), [[2, 3]], None)
    assert t.recursive_sequence_lengths() == [[2, 3]]
    assert t.has_valid_recursive_sequence_lengths()
    with pytest.raises(AssertionError):
        fluid.create_lod_tensor(np.ones((5, 3), "f4"), [[2, 2]], None)
    r = fluid.create_random_int_lodtensor([[2, 1]], [4], None, 0, 9)
    assert tuple(r.shape) == (3, 4)
    arr = r.numpy()
    assert arr.min() >= 0 and arr.max() <= 9


def test_weighted_average_and_helpers(capsys):
    from paddle_tpu.fluid.average import WeightedAverage
    wa = WeightedAverage()
    with pytest.raises(ValueError):
        wa.eval()
    wa.add(1.0, 1)
    wa.add(3.0, 3)
    assert wa.eval() == pytest.approx(2.5)
    with pytest.raises(ValueError):
        wa.add("x", 1)

    from paddle_tpu.fluid.annotations import deprecated

    @deprecated(since="1.0", instead="new_api")
    def old(v):
        return v + 1

    assert old(1) == 2
    assert "deprecated since 1.0" in capsys.readouterr().err

    from paddle_tpu.fluid.log_helper import get_logger
    import logging
    lg = get_logger("t5", logging.INFO, fmt="%(message)s")
    assert get_logger("t5", logging.INFO) is lg
    assert len(lg.handlers) == 1  # no duplicate handlers

    from paddle_tpu.fluid.wrapped_decorator import (
        wrap_decorator, signature_safe_contextmanager)

    def dec(f):
        def inner(*a):
            return f(*a) * 10
        return inner

    @wrap_decorator(dec)
    def g(v):
        """doc"""
        return v

    assert g(2) == 20 and g.__doc__ == "doc"

    @signature_safe_contextmanager
    def ctx(v):
        yield v * 2

    with ctx(3) as got:
        assert got == 6


def test_default_scope_funcs():
    from paddle_tpu.fluid import default_scope_funcs as dsf
    base = dsf.get_cur_scope()
    dsf.enter_local_scope()
    dsf.var("a")
    dsf.get_cur_scope().vars["a"] = 7
    assert dsf.find_var("a") == 7
    dsf.leave_local_scope()
    assert dsf.get_cur_scope() is base
    assert dsf.scoped_function(lambda: 42) == 42


def test_fetch_handler_surface():
    from paddle_tpu.fluid.trainer_factory import (FetchHandler,
                                                  FetchHandlerMonitor)
    with pytest.raises(ValueError):
        FetchHandler(None)

    class V:
        name = "v"
    h = FetchHandler(var_dict={"v": V()}, period_secs=60)
    scope = static.Scope()
    scope.vars["v"] = 3
    m = FetchHandlerMonitor(scope, h)
    m.start()
    m.stop()
    from paddle_tpu.fluid.trainer_desc import DownpourSGDOPT
    from paddle_tpu.fluid import device_worker
    assert device_worker.DownpourSGDOPT is DownpourSGDOPT


# ---- review-pass regressions -------------------------------------------------

def test_fluid_embedding_callable():
    """fluid.embedding (input.py signature, incl. is_distributed) must
    actually run, not just resolve."""
    ids = pt.to_tensor(np.array([[1], [3]], "i4"))
    out = fluid.embedding(ids, (10, 4), is_distributed=True)
    assert tuple(out.shape)[-1] == 4


def test_static_mode_rejects_dygraph_decay():
    pt.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog, static.Program()):
            x = static.data("x", [None, 2], "float32")
            loss = fluid.layers.reduce_mean(fluid.layers.fc(x, size=1))
            o = optimizer.SGD(
                learning_rate=dygraph.ExponentialDecay(0.1, 1, 0.5))
            with pytest.raises(TypeError, match="dygraph-only"):
                o.minimize(loss)
    finally:
        pt.disable_static()


def test_decay_get_lr_before_first_step():
    w = pt.Parameter(np.zeros((1,), "f4"))
    o = optimizer.SGD(
        learning_rate=dygraph.PiecewiseDecay([5], [0.3, 0.1], begin=0),
        parameters=[w])
    assert o.get_lr() == pytest.approx(0.3)


def test_dataset_int_slots_preserve_large_ids(tmp_path):
    big = 2 ** 24 + 1  # not representable in float32
    f = tmp_path / "ids.txt"
    f.write_text(f"2 {big} 7 1 0.5\n")

    class V:
        def __init__(self, name, dtype):
            self.name, self.dtype = name, dtype
    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(1)
    ds.set_filelist([str(f)])
    ds.set_use_var([V("ids", "int64"), V("val", "float32")])
    ds.load_into_memory()
    batch = next(iter(ds._batches()))
    assert batch["ids"].dtype == np.int64
    assert batch["ids"][0, 0] == big
    assert batch["val"].dtype == np.float32


def test_dataset_pipe_command_blank_lines(tmp_path):
    f = tmp_path / "p.txt"
    f.write_text("1 1.0 1 2.0\n\n1 3.0 1 4.0\n")

    class V:
        def __init__(self, name):
            self.name = name
    ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    ds.set_batch_size(2)
    ds.set_filelist([str(f)])
    ds.set_pipe_command("sed s/x/x/")  # non-cat pipe passthrough
    ds.set_use_var([V("a"), V("b")])
    ds.load_into_memory()
    assert ds.get_memory_data_size() == 2  # blank line skipped


# ---- top-level paddle tail (r5 full-tree sweep) -----------------------------

def test_unbind_and_diag_embed():
    t = pt.to_tensor(np.arange(6).reshape(2, 3).astype("f4"))
    parts = pt.unbind(t, axis=0)
    assert len(parts) == 2 and tuple(parts[0].shape) == (3,)
    np.testing.assert_allclose(parts[1].numpy(), [3, 4, 5])
    s = (parts[0] * 2).sum()
    s.backward()  # differentiable through the list output

    d = pt.diag_embed(pt.to_tensor(np.array([1., 2.], "f4")), offset=1)
    np.testing.assert_allclose(
        d.numpy(), [[0, 1, 0], [0, 0, 2], [0, 0, 0]])


def test_compose_not_aligned_exception():
    from paddle_tpu import reader

    def r1():
        yield from [1, 2, 3]

    def r2():
        yield from [4, 5]

    with pytest.raises(reader.ComposeNotAligned):
        list(reader.compose(r1, r2)())
    assert issubclass(reader.ComposeNotAligned, ValueError)
    got = list(reader.compose(r1, r2, check_alignment=False)())
    assert got == [(1, 4), (2, 5)]


def test_utils_profiler_classes():
    from paddle_tpu.utils import (Profiler, ProfilerOptions, get_profiler,
                                  Ploter)
    opts = ProfilerOptions({"state": "CPU"})
    assert opts["state"] == "CPU"
    assert opts["profile_path"] is None  # 'none' -> None
    with pytest.raises(ValueError):
        opts["no_such_option"]
    p = Profiler(enabled=False)
    with p:
        p.record_step()
    assert p.batch_id == 0  # disabled: no counting
    assert get_profiler() is not None

    pl = Ploter("train", "test")
    pl.append("train", 0, 1.0)
    pl.append("train", 1, 0.5)
    with pytest.raises(ValueError):
        pl.append("nope", 0, 1.0)
    assert pl.__plot_data__["train"].value == [1.0, 0.5]
    pl.reset()
    assert pl.__plot_data__["train"].value == []


def test_fs_wrapper_localfs(tmp_path):
    from paddle_tpu.distributed.fs_wrapper import FS, LocalFS, BDFS
    fs = LocalFS()
    d = tmp_path / "a"
    fs.mkdir(str(d))
    assert fs.stat(str(d))
    (d / "x.txt").write_text("hi")
    assert fs.ls_dir(str(d)) == ["x.txt"]
    assert fs.list_dirs(str(tmp_path)) == ["a"]
    fs.download(str(d / "x.txt"), str(tmp_path / "y.txt"))
    assert (tmp_path / "y.txt").read_text() == "hi"
    fs.delete(str(d))
    assert not fs.stat(str(d))
    assert not fs.need_upload_download()
    assert issubclass(LocalFS, FS)
    with pytest.raises(RuntimeError):
        BDFS()


def test_dataset_tail_helpers(tmp_path, monkeypatch):
    from paddle_tpu.dataset import common, imdb, movielens

    monkeypatch.chdir(tmp_path)

    def rdr():
        yield from range(25)

    files = common.split(rdr, 10)
    assert len(files) >= 2
    back = sorted(common.cluster_files_reader(
        str(tmp_path / "*.pickle"), 1, 0)())
    assert back == list(range(25))
    # two trainers partition the files disjointly
    a = list(common.cluster_files_reader(str(tmp_path / "*.pickle"),
                                         2, 0)())
    b = list(common.cluster_files_reader(str(tmp_path / "*.pickle"),
                                         2, 1)())
    assert sorted(a + b) == list(range(25))

    assert imdb.build_dict() == imdb.word_dict()
    assert len(movielens.movie_categories()) == movielens.NUM_CATEGORIES
    assert len(movielens.get_movie_title_dict()) == movielens.TITLE_VOCAB


def test_nn_functional_one_x_surface():
    from paddle_tpu.nn import functional as F
    x = pt.to_tensor(np.array([[-1.0, 0.5]], "f4"))
    out = F.logsigmoid(x)
    np.testing.assert_allclose(
        out.numpy(), np.log(1 / (1 + np.exp([[1.0, -0.5]]))), rtol=1e-5)
    assert callable(F.roi_align) and callable(F.yolov3_loss)
    assert callable(F.noam_decay) and callable(F.tanh_shrink)


def test_profiler_batch_range_starts_mid_run(monkeypatch):
    """Review regression: batch_range [2, 3] must START the trace at
    batch 2 (the old `_current_profiler is self` gate never did)."""
    from paddle_tpu.utils import profiler as prof
    calls = []

    def fake_start(**kw):
        calls.append("start")
        prof._profiling_active = True

    def fake_stop(**kw):
        calls.append("stop")
        prof._profiling_active = False

    monkeypatch.setattr(prof, "start_profiler", fake_start)
    monkeypatch.setattr(prof, "stop_profiler", fake_stop)
    monkeypatch.setattr(prof, "_profiling_active", False)
    opts = prof.ProfilerOptions({"batch_range": [2, 3]})
    with prof.Profiler(enabled=True, options=opts) as p:
        for _ in range(4):
            p.record_step()
    assert "start" in calls, calls
    assert calls.index("start") < calls.index("stop")


def test_dataset_native_parse_matches_python(tmp_path):
    """The C MultiSlot parser (csrc ptc_multislot_parse) and the python
    fallback produce identical batches — including full-range int64 ids
    a float64 lane would corrupt — and both reject malformed text."""
    big = 2 ** 62 + 12345  # beyond float64's 2^53 exact-integer range
    f = tmp_path / "m.txt"
    f.write_text(
        f"2 {big} 7 2 0.5 -1.25\n"
        "1 42 1 3.75\n"
        "\n"  # blank lines are plain whitespace in the token stream
        "3 1 2 3 0\n")  # zero-count float slot

    class V:
        def __init__(self, name, dtype):
            self.name, self.dtype = name, dtype

    def load(use_native):
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_batch_size(4)
        ds.set_filelist([str(f)])
        ds.set_use_var([V("ids", "int64"), V("x", "float32")])
        ds.use_native_parse = use_native
        ds.load_into_memory()
        return list(ds._batches())

    native_b = load(True)
    python_b = load(False)
    assert len(native_b) == len(python_b) == 1
    for key in ("ids", "x"):
        np.testing.assert_array_equal(native_b[0][key], python_b[0][key])
    assert native_b[0]["ids"].dtype == np.int64
    assert native_b[0]["ids"][0, 0] == big  # exact through the i64 lane

    # malformed: truncated record
    from paddle_tpu.io import native
    with pytest.raises(ValueError):
        native.multislot_parse(b"2 1.0", 2, [False, False])
    with pytest.raises(ValueError):
        native.multislot_parse(b"x 1.0 1 2.0", 2, [False, False])


def test_dataset_native_rejects_misaligned_tokens(tmp_path):
    """Review regression: a float count token ('1.5') must be rejected
    by BOTH parsers, not silently consumed as count 1 + value 0.5."""
    from paddle_tpu.io import native
    with pytest.raises(ValueError):
        native.multislot_parse(b"1.5 2.0 3.0", 1, [False])

    f = tmp_path / "bad.txt"
    f.write_text("1.5 2.0 3.0\n")

    class V:
        def __init__(self, name):
            self.name = name
    for use_native in (True, False):
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_filelist([str(f)])
        ds.set_use_var([V("x")])
        ds.use_native_parse = use_native
        with pytest.raises(ValueError):
            ds.load_into_memory()


def test_dataset_columnar_batches_match_python_after_shuffle(tmp_path):
    """The columnar (native-parse) batch assembler must produce the
    SAME batches as the python record path — including after
    local_shuffle (both draw the same RandomState permutation)."""
    f = tmp_path / "c.txt"
    rng = np.random.RandomState(3)
    with open(f, "w") as fh:
        for _ in range(23):
            n = rng.randint(1, 5)
            ids = rng.randint(0, 10**7, n)
            fh.write(f"{n} " + " ".join(map(str, ids)) +
                     f" 1 {rng.rand():.4f}\n")

    class V:
        def __init__(self, name, dtype):
            self.name, self.dtype = name, dtype

    def batches(use_native):
        pt.seed(7)  # same shuffle seed both paths
        ds = fluid.DatasetFactory().create_dataset("InMemoryDataset")
        ds.set_batch_size(6)
        ds.set_filelist([str(f)])
        ds.set_use_var([V("ids", "int64"), V("x", "float32")])
        ds.use_native_parse = use_native
        ds.load_into_memory()
        ds.local_shuffle()
        return list(ds._batches())

    nat = batches(True)
    py = batches(False)
    assert len(nat) == len(py) == 4  # 23 records / 6
    for a, b in zip(nat, py):
        for key in ("ids", "x"):
            np.testing.assert_array_equal(a[key], b[key])
    assert nat[0]["ids"].dtype == np.int64
