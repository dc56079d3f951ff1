"""Step-pipelining stack (ISSUE 2): device prefetch, shape bucketing,
async fetches, AOT warmup, the persistent compilation cache, and the
executor cache-key mesh regression — all observable through the monitor
counters docs/performance.md documents."""
import threading
import time

import numpy as np
import pytest
import jax

import paddle_tpu as pt
from paddle_tpu import io, jit, nn, hapi, static, optimizer as opt
from paddle_tpu.fluid import layers as FL
from paddle_tpu.io.bucketing import (next_bucket, pad_to_bucket,
                                     batch_mask, pad_feed_dict)


@pytest.fixture
def mon():
    from paddle_tpu import monitor
    monitor.reset()
    monitor.enable()
    yield monitor
    monitor.disable()
    monitor.reset()


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name == "paddle_tpu-prefetch" and t.is_alive()]


# ---------------------------------------------------------------------------
# bucketing primitives

def test_next_bucket_pow2_and_explicit():
    assert next_bucket(12) == 16
    assert next_bucket(32) == 32
    assert next_bucket(33) == 64
    assert next_bucket(12, [32]) == 32
    assert next_bucket(40, [8, 32]) == 40  # past the largest: exact
    assert next_bucket(5, [8, 32]) == 8


def test_pad_to_bucket_modes():
    a = np.arange(6, dtype="f4").reshape(3, 2)
    r = pad_to_bucket(a, 5)  # repeat
    assert r.shape == (5, 2)
    np.testing.assert_array_equal(r[3], a[-1])
    np.testing.assert_array_equal(r[4], a[-1])
    z = pad_to_bucket(a, 5, mode="zeros")
    np.testing.assert_array_equal(z[3:], np.zeros((2, 2), "f4"))
    import jax.numpy as jnp
    j = pad_to_bucket(jnp.asarray(a), 4)
    assert isinstance(j, jax.Array) and j.shape == (4, 2)
    with pytest.raises(ValueError):
        pad_to_bucket(a, 2)
    m = batch_mask(3, 5)
    np.testing.assert_array_equal(m, [1, 1, 1, 0, 0])


def test_pad_feed_dict_consistent_and_ragged():
    feed = {"x": np.ones((12, 4), "f4"), "y": np.ones((12, 1), "f4")}
    out, real_n, padded_n = pad_feed_dict(feed, buckets=[32])
    assert (real_n, padded_n) == (12, 32)
    assert out["x"].shape == (32, 4) and out["y"].shape == (32, 1)
    # inconsistent batch dims: no slicing info
    out2, r2, p2 = pad_feed_dict({"a": np.ones((3, 2)),
                                  "b": np.ones((5, 2))})
    assert (r2, p2) == (None, None)
    assert out2["a"].shape == (4, 2) and out2["b"].shape == (8, 2)


# ---------------------------------------------------------------------------
# prefetch_to_device

def test_prefetch_order_and_device_placement(mon):
    batches = [{"x": np.full((4, 2), i, "f4"), "y": np.array([i], "i4")}
               for i in range(10)]
    got = list(io.prefetch_to_device(iter(batches), size=3))
    assert len(got) == 10
    for i, b in enumerate(got):
        assert isinstance(b["x"], jax.Array)  # already device-resident
        assert float(b["x"][0, 0]) == i       # order preserved
    assert mon.registry().value("prefetch.batches") == 10
    assert not _prefetch_threads()  # worker joined at exhaustion


def test_prefetch_mesh_sharding():
    from jax.sharding import Mesh
    devs = jax.devices()
    assert len(devs) == 8, "conftest forces an 8-device CPU mesh"
    mesh = Mesh(np.array(devs), ("dp",))
    batches = [(np.arange(16, dtype="f4").reshape(16, 1),
                np.float32(0.5))]  # scalar leaf: replicates
    (xb, sb), = list(io.prefetch_to_device(iter(batches), mesh=mesh))
    assert len(xb.sharding.device_set) == 8
    assert not xb.sharding.is_fully_replicated  # batch-sharded
    assert sb.sharding.is_fully_replicated
    # 1-device mesh: everything lands on that one device
    mesh1 = Mesh(np.array(devs[:1]), ("dp",))
    (xb1, _), = list(io.prefetch_to_device(iter(batches), mesh=mesh1))
    assert xb1.sharding.device_set == {devs[0]}


def test_prefetch_shutdown_no_thread_leak():
    def gen():
        for i in range(1000):
            yield np.full((2,), i, "f4")

    it = io.prefetch_to_device(gen(), size=2)
    first = next(it)
    assert float(first[0]) == 0
    it.close()  # abandoning mid-stream must stop + join the producer
    deadline = time.time() + 5
    while _prefetch_threads() and time.time() < deadline:
        time.sleep(0.01)
    assert not _prefetch_threads()


def test_prefetch_propagates_producer_error():
    def gen():
        yield np.zeros((2,), "f4")
        raise RuntimeError("boom in the pipeline")

    it = io.prefetch_to_device(gen())
    next(it)
    with pytest.raises(RuntimeError, match="boom in the pipeline"):
        next(it)
    assert not _prefetch_threads()


def test_dataloader_prefetch_to_device_param():
    x = np.random.RandomState(0).rand(20, 3).astype("f4")
    dl = io.DataLoader(io.TensorDataset(x), batch_size=8,
                       prefetch_to_device=2)
    seen = 0
    for (xb,) in dl:
        assert isinstance(xb, jax.Array)
        seen += xb.shape[0]
    assert seen == 20
    assert not _prefetch_threads()


def test_dataloader_threaded_iterator_shutdown():
    x = np.random.RandomState(0).rand(400, 3).astype("f4")
    dl = io.DataLoader(io.TensorDataset(x), batch_size=2, use_native=False,
                       prefetch_factor=2)
    before = threading.active_count()
    it = iter(dl)
    next(it)
    it.close()  # abandoned epoch: producer must unblock from q.put + join
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= before


# ---------------------------------------------------------------------------
# Executor: cache key, bucketing, async fetch, warmup

def _build_program(din=8):
    prog, sprog = static.Program(), static.Program()
    with static.program_guard(prog, sprog):
        x = static.data("x", [None, din], "float32")
        y = static.data("y", [None, 1], "float32")
        h = FL.fc(x, 16, act="relu")
        out = FL.fc(h, 1)
        loss = ((out - y) ** 2).mean()
        opt.SGD(learning_rate=0.05).minimize(loss)
    return prog, sprog, loss, out


def _data(n, din=8, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, din).astype("f4")
    return x, (x.sum(-1, keepdims=True) * 0.5).astype("f4")


def test_executor_cache_key_includes_mesh():
    """Regression (ISSUE 2 satellite): a plain run and a
    with_data_parallel run with IDENTICAL feed shapes must compile two
    distinct executables, not collide on one cache slot."""
    pt.enable_static()
    try:
        prog, sprog, loss, _ = _build_program()
        exe = static.Executor()
        exe.run(sprog)
        x, y = _data(64)
        plain = exe.run(prog, feed={"x": x, "y": y}, fetch_list=[loss])
        cp = static.CompiledProgram(prog).with_data_parallel(
            loss_name=loss.name)
        exe.run(cp, feed={"x": x, "y": y}, fetch_list=[loss])
        assert len(exe._cache) == 2
        keys = list(exe._cache)
        assert keys[0][:2] == keys[1][:2]      # same program
        assert keys[0][3] != keys[1][3]        # different mesh signature
        assert np.isfinite(plain[0]).all()
    finally:
        pt.disable_static()


def test_executor_feed_keying_skips_device_transfer(mon):
    """Satellite: shapes/dtypes for the cache key come from the HOST
    arrays — and jnp.asarray's x64-off canonicalization is mirrored, so
    a float64/int64 feed hits the float32/int32 executable."""
    pt.enable_static()
    try:
        prog, sprog, loss, _ = _build_program()
        exe = static.Executor()
        exe.run(sprog)
        x, y = _data(16)
        exe.run(prog, feed={"x": x, "y": y}, fetch_list=[loss])
        exe.run(prog, feed={"x": x.astype("f8"), "y": y.astype("f8")},
                fetch_list=[loss])
        reg = mon.registry()
        assert reg.value("executor.compile") == 1
        assert reg.value("executor.cache_hit") == 1
    finally:
        pt.disable_static()


def test_executor_bucketing_single_compile_and_fetch_slicing(mon):
    pt.enable_static()
    try:
        prog, sprog, loss, out = _build_program()
        exe = static.Executor()
        exe.run(sprog)
        x, y = _data(300)
        for i in range(0, 300, 32):  # 9 full batches + a ragged 12
            res = exe.run(prog, feed={"x": x[i:i + 32], "y": y[i:i + 32]},
                          fetch_list=[loss, out], bucket=True,
                          buckets=[32])
        reg = mon.registry()
        assert reg.value("executor.compile") == 1
        assert reg.value("executor.recompile") == 0
        assert reg.value("executor.bucket_pad") == 1
        assert res[1].shape == (12, 1)  # per-example fetch sliced back

        # repeat-padding leaves the real rows' forward untouched: clone
        # the current params (host copies — donation would invalidate a
        # shared device buffer) and compare bucketed vs exact-shape runs
        prog2, sprog2, _, out2 = _build_program()
        exe2 = static.Executor()
        exe2.run(sprog2)
        for holder, src in zip(prog2.param_vars.values(),
                               prog.param_vars.values()):
            holder.data = np.asarray(src.data).copy()
        exact = exe2.run(prog2, feed={"x": x[288:], "y": y[288:]},
                         fetch_list=[out2])
        padded = exe.run(prog, feed={"x": x[288:], "y": y[288:]},
                         fetch_list=[loss, out], bucket=True,
                         buckets=[32])
        np.testing.assert_allclose(padded[1], exact[0], rtol=2e-5,
                                   atol=1e-6)
    finally:
        pt.disable_static()


def test_executor_recompile_counter_without_bucketing(mon):
    pt.enable_static()
    try:
        prog, sprog, loss, _ = _build_program()
        exe = static.Executor()
        exe.run(sprog)
        for n in (32, 12):  # second shape = the avoidable recompile
            x, y = _data(n)
            exe.run(prog, feed={"x": x, "y": y}, fetch_list=[loss])
        reg = mon.registry()
        assert reg.value("executor.compile") == 2
        assert reg.value("executor.recompile") == 1
    finally:
        pt.disable_static()


def test_executor_async_fetch_lag_and_flush(mon):
    pt.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog, static.Program()):
            x = static.data("x", [None, 2], "float32")
            out = x * 2.0
        exe = static.Executor()
        vals = [np.full((4, 2), i, "f4") for i in range(3)]
        got = [exe.run(prog, feed={"x": v}, fetch_list=[out],
                       async_fetch=True) for v in vals]
        assert got[0] is None                      # nothing pending yet
        assert float(got[1][0][0, 0]) == 0.0       # step 0's fetch
        assert float(got[2][0][0, 0]) == 2.0       # step 1's fetch
        last = exe.flush_fetches()
        assert float(last[0][0, 0]) == 4.0         # step 2's fetch
        assert exe.flush_fetches() is None
        reg = mon.registry()
        assert reg.value("executor.fetch_blocking") == 0
        assert reg.value("executor.fetch_async") == 3
    finally:
        pt.disable_static()


def test_executor_fetch_period(mon):
    pt.enable_static()
    try:
        prog = static.Program()
        with static.program_guard(prog, static.Program()):
            x = static.data("x", [None], "float32")
            out = x + 1.0
        exe = static.Executor()
        got = [exe.run(prog, feed={"x": np.full((2,), i, "f4")},
                       fetch_list=[out], fetch_period=2)
               for i in range(4)]
        assert got[0] is None and got[2] is None
        assert got[1] is not None and got[3] is not None
        assert mon.registry().value("executor.fetch_skipped") == 2
    finally:
        pt.disable_static()


def test_executor_warmup_aot_precompiles(mon):
    pt.enable_static()
    try:
        prog, sprog, loss, _ = _build_program()
        exe = static.Executor()
        exe.run(sprog)
        key = exe.warmup(prog, feed_specs={"x": ((32, 8), "float32"),
                                           "y": ((32, 1), "float32")},
                         fetch_list=[loss], bucket=True, buckets=[32])
        assert key in exe._cache
        reg = mon.registry()
        assert reg.value("executor.aot_warmup") == 1
        assert reg.value("executor.compile") == 1
        x, y = _data(12)  # ragged: buckets to the warmed 32-row shape
        res = exe.run(prog, feed={"x": x, "y": y}, fetch_list=[loss],
                      bucket=True, buckets=[32])
        assert reg.value("executor.compile") == 1  # no new executable
        assert reg.value("executor.cache_hit") == 1
        assert np.isfinite(res[0]).all()
    finally:
        pt.disable_static()


def test_train_from_dataset_prefetch_and_bucket(mon):
    pt.enable_static()
    try:
        from paddle_tpu.fluid.dataset import InMemoryDataset
        prog, sprog, loss, _ = _build_program(din=4)
        exe = static.Executor()
        exe.run(sprog)
        ds = InMemoryDataset()
        ds.set_use_var([prog.feed_vars["x"], prog.feed_vars["y"]])
        ds.set_batch_size(8)
        rng = np.random.RandomState(0)
        # resident records, MultiSlot layout: [x slot values, y slot]
        ds._memory = [[[float(v) for v in rng.rand(4)], [0.5]]
                      for _ in range(20)]  # 2 full batches + ragged 4
        exe.train_from_dataset(prog, dataset=ds, fetch_list=[loss],
                               prefetch=2, bucket=True, buckets=[8])
        reg = mon.registry()
        assert reg.value("executor.compile") == 1
        assert reg.value("prefetch.batches") == 3
    finally:
        pt.disable_static()


# ---------------------------------------------------------------------------
# to_static bucketing + hapi fit

def test_to_static_bucketing_single_compile(mon):
    lin = nn.Linear(4, 2)

    @jit.to_static(models=[lin], bucket=True, buckets=[16])
    def fwd(x):
        return lin(x)

    full = fwd(pt.to_tensor(np.ones((16, 4), "f4")))
    ragged = fwd(pt.to_tensor(np.ones((5, 4), "f4")))
    assert tuple(ragged.shape) == (5, 2)  # output sliced to real length
    np.testing.assert_allclose(ragged.numpy(), full.numpy()[:5],
                               rtol=1e-6)
    reg = mon.registry()
    assert reg.value("jit.compile") == 1
    assert reg.value("jit.recompile") == 0
    assert reg.value("jit.bucket_pad") == 1
    assert reg.value("jit.cache_hit") == 1


def test_hapi_fit_bucket_and_prefetch(mon):
    pt.seed(0)
    rng = np.random.RandomState(0)
    x = rng.rand(40, 8).astype("f4")
    y = (x.sum(-1, keepdims=True) * 0.5).astype("f4")
    m = hapi.Model(nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                                 nn.Linear(16, 1)))
    m.prepare(optimizer=opt.SGD(learning_rate=0.05,
                                parameters=m.parameters()),
              loss_function=lambda o, lab: [((o - lab[0]) ** 2).mean()])
    hist = m.fit(io.TensorDataset(x, y), batch_size=32, epochs=3,
                 verbose=0, shuffle=False, bucket=True, prefetch=1)
    assert len(hist["loss"]) == 3
    assert hist["loss"][-1] < hist["loss"][0]
    reg = mon.registry()
    # 32-row + ragged 8-row batches share ONE executable
    assert reg.value("jit.compile") == 1
    assert reg.value("jit.recompile") == 0
    assert reg.value("jit.bucket_pad") == 3  # one ragged batch per epoch
    assert reg.value("prefetch.batches") == 6
    assert not _prefetch_threads()


# ---------------------------------------------------------------------------
# persistent compilation cache

@pytest.mark.parametrize("env_set", [False, True],
                         ids=["env-unset", "env-set"])
def test_enable_compilation_cache(tmp_path, monkeypatch, env_set):
    """One rule: JAX_COMPILATION_CACHE_DIR wins and no other directory
    is set in code; unset, the cache is one fixed directory inside the
    checkout."""
    import os
    from paddle_tpu import device
    old = jax.config.jax_compilation_cache_dir
    old_min = jax.config.jax_persistent_cache_min_compile_time_secs
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert device._CACHE_DIR_DEFAULT == os.path.join(repo, ".jax_cache")
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / "outside"))
            jax.config.update("jax_compilation_cache_dir", "sentinel")
            assert pt.enable_compilation_cache() == \
                str(tmp_path / "outside")
            # JAX reads the variable itself; the code set nothing
            assert jax.config.jax_compilation_cache_dir == "sentinel"
            assert not os.path.exists(tmp_path / "outside")
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            monkeypatch.setattr(device, "_CACHE_DIR_DEFAULT",
                                str(tmp_path / ".jax_cache"))
            p = pt.enable_compilation_cache()
            assert p == str(tmp_path / ".jax_cache") and os.path.isdir(p)
            assert jax.config.jax_compilation_cache_dir == p
    finally:
        jax.config.update("jax_compilation_cache_dir", old)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          old_min)
