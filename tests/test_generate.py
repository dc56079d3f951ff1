"""Continuous-batching generative decode (PR 15): the KV-cache pool's
slot/capacity/byte discipline, the GenerateEngine's bit-parity with
both a full-recompute reference and the classic single-sequence
``nn.decode`` stack, zero-recompile churn, the continuous-vs-drain
refill A/B, ragged-prompt coalescing in the fixed-shape engine, and
the decode-SLO supervisor scale-up. All CPU, all fast."""
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import inference, nn, serving
from paddle_tpu.io.bucketing import grow_buckets, next_bucket
from paddle_tpu.nn import decode as nnd
from paddle_tpu.serving import kv_cache
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.serving.generate import GenerateEngine, MultiDecodeEngine
from paddle_tpu.serving.supervisor import ServingSupervisor


@pytest.fixture(scope="module")
def model():
    return serving.demo_model(vocab=32, dim=16, heads=2, layers=2,
                              max_len=64, seed=1)


def _greedy_recompute(model, prompt, n, eos=None):
    """Reference decode: full-prompt recompute per step, no KV cache."""
    seq = list(prompt)
    out = []
    for _ in range(n):
        toks = jnp.asarray([seq], jnp.int32)
        _, last = model.prefill_fn(model.state, toks,
                                   jnp.asarray([len(seq)], jnp.int32))
        t = int(jnp.argmax(last, axis=-1)[0])
        seq.append(t)
        out.append(t)
        if eos is not None and t == eos:
            break
    return out


# ---------------------------------------------------------------------------
# grow_buckets (satellite 1): the closed geometric family


def test_grow_buckets_monotone_and_covers_cap():
    for base in (1, 3, 16, 64):
        for factor in (1.3, 1.5, 2.0, 3.0):
            for cap in (base, base + 1, base * 7, 1024):
                if cap < base:
                    continue
                fam = grow_buckets(base, factor, cap)
                assert fam[0] == base
                assert fam[-1] >= cap
                assert all(b < a for b, a in zip(fam, fam[1:]))
                assert all(isinstance(b, int) for b in fam)


def test_grow_buckets_stable_family_key():
    a = grow_buckets(16, 2.0, 100)
    b = grow_buckets(16, 2.0, 100)
    assert isinstance(a, tuple) and a == b and hash(a) == hash(b)
    assert a == (16, 32, 64, 128)
    # a different family never aliases the same key
    assert grow_buckets(16, 3.0, 100) != a


def test_grow_buckets_validation():
    with pytest.raises(ValueError):
        grow_buckets(0, 2.0, 8)
    with pytest.raises(ValueError):
        grow_buckets(8, 1.0, 64)
    with pytest.raises(ValueError):
        grow_buckets(8, 2.0, None)
    with pytest.raises(ValueError):
        grow_buckets(8, 2.0, 4)


def test_grow_buckets_near_one_factor_still_increases():
    fam = grow_buckets(4, 1.01, 12)
    assert all(b < a for b, a in zip(fam, fam[1:]))
    assert fam[-1] >= 12


# ---------------------------------------------------------------------------
# KVCachePool: slots, capacity schedule, byte honesty


SPEC = {"k0": ((2, 8), "float32"), "v0": ((2, 8), "float32")}


def test_pool_alloc_free_cycle():
    pool = kv_cache.KVCachePool(SPEC, slots=2, page=16, max_len=32)
    a, b = pool.alloc(), pool.alloc()
    assert {a, b} == {0, 1}
    assert pool.alloc() is None
    assert pool.used_slots() == 2 and pool.free_slots() == 0
    pool.free(a)
    assert pool.alloc() == a
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free(a)


def test_pool_capacity_schedule():
    pool = kv_cache.KVCachePool(SPEC, slots=2, page=16, factor=2.0,
                                max_len=64)
    assert pool.seq_buckets == (16, 32, 64)
    assert pool.capacity == 16
    assert pool.capacity_for(16) == 16
    assert pool.capacity_for(17) == 32
    assert not pool.needs_growth(16)
    assert pool.needs_growth(33)
    with pytest.raises(ValueError):
        pool.capacity_for(65)
    with pytest.raises(ValueError):
        pool.grow_to(48, lambda bufs, old, new: bufs)  # not in family


def test_pool_bytes_accounting():
    pool = kv_cache.KVCachePool(SPEC, slots=4, page=16, factor=2.0,
                                max_len=64)
    per_tok = kv_cache.bytes_per_token(SPEC)
    assert per_tok == 2 * 2 * 8 * 4
    assert pool.bytes() == 4 * 16 * per_tok == pool.allocated_bytes()
    assert pool.max_bytes() == 4 * 64 * per_tok

    def grow(bufs, old, new):
        return {k: jnp.pad(v, [(0, 0), (0, new - old)]
                           + [(0, 0)] * (v.ndim - 2))
                for k, v in bufs.items()}

    pool.grow_to(32, grow)
    assert pool.capacity == 32
    assert pool.bytes() == pool.allocated_bytes() == 4 * 32 * per_tok
    assert pool.stats()["grows"] == 1


def test_fits_budget_and_plan_slots():
    per_tok = kv_cache.bytes_per_token(SPEC)
    need = 4 * 64 * per_tok
    fits, needed, lim = kv_cache.fits_budget(SPEC, 4, 64,
                                             limit_bytes=need)
    assert fits and needed == need and lim == need
    fits, _, _ = kv_cache.fits_budget(SPEC, 4, 64, limit_bytes=need - 1)
    assert not fits
    # reserve half the budget -> half the slots fit
    assert kv_cache.plan_slots(SPEC, 64, limit_bytes=2 * need,
                               reserve_frac=0.5) == 4
    assert kv_cache.fits_budget(SPEC, 4, 64, limit_bytes=None)[0] in \
        (None, True, False)  # no-budget CPU: never invents a verdict


# ---------------------------------------------------------------------------
# GenerateEngine: bit-parity, churn, zero recompiles


def test_engine_parity_three_way(model):
    """Engine under slot churn == full recompute == the classic
    nn.decode single-sequence stack (KVCacheCell + BasicDecoder +
    GreedyEmbeddingHelper), token for token, every request."""
    max_new = 12
    prompts = [[1, 2, 3], [5, 4, 3, 2, 1, 9, 8], [7] * 11]
    eng = GenerateEngine(model, slots=2, page=16, factor=2.0,
                         max_len=64, prompt_buckets=(4, 8, 16),
                         start=False, shed=False)
    futs = [eng.submit(p, max_new_tokens=max_new, eos_token=None)
            for p in prompts]
    for _ in range(80):
        eng.tick()
    got = [list(map(int, f.result(timeout=10))) for f in futs]
    eng.close()

    for p, toks in zip(prompts, got):
        assert toks == _greedy_recompute(model, p, max_new)

        # the single-sequence twin: prefill seeds the cell, the helper
        # feeds argmax ids back through an identity embedding
        pl = jnp.asarray([len(p)], jnp.int32)
        kv, last = model.prefill_fn(model.state,
                                    jnp.asarray([p], jnp.int32), pl)
        first = int(jnp.argmax(last, axis=-1)[0])
        cell = nnd.KVCacheCell(model.decode_fn, model.state, max_len=64)
        helper = nnd.GreedyEmbeddingHelper(
            lambda t: t, jnp.asarray([first], jnp.int32), end_token=-1)
        _, sids, _ = nnd.basic_decode(nnd.BasicDecoder(cell, helper),
                                      cell.init_states(kv, pl),
                                      max_step_num=max_new - 1)
        twin = [first] + list(map(int, np.asarray(sids.data)[0]))
        assert toks == twin


def test_engine_eos_early_stop(model):
    # the stop token is read off the model's own unbounded stream (the
    # first token that makes its first appearance past index 0), not
    # pinned to what one XLA build happened to emit for this seed
    full = _greedy_recompute(model, [1, 2, 3], 12)
    eos = next(t for i, t in enumerate(full) if i and t not in full[:i])
    ref = _greedy_recompute(model, [1, 2, 3], 12, eos=eos)
    assert ref[-1] == eos and len(ref) < 12
    eng = GenerateEngine(model, slots=1, page=16, factor=2.0,
                         max_len=64, prompt_buckets=(4,),
                         start=False, shed=False)
    fut = eng.submit([1, 2, 3], max_new_tokens=12, eos_token=eos)
    for _ in range(20):
        eng.tick()
    assert list(map(int, fut.result(timeout=10))) == ref
    eng.close()


def test_zero_compiles_under_churn(model):
    """Join/leave churn after warmup mints no executable and performs
    no retrace — the acceptance criterion that makes continuous
    batching TPU-viable."""
    eng = GenerateEngine(model, slots=3, page=16, factor=2.0,
                         max_len=32, prompt_buckets=(4, 8),
                         start=False, shed=False)
    eng.warmup()
    n_exec, n_trace = eng.executables()
    rng = np.random.RandomState(3)
    futs = []
    for i in range(14):
        plen = int(rng.randint(1, 9))
        futs.append(eng.submit(rng.randint(1, 31, size=plen).tolist(),
                               max_new_tokens=int(rng.randint(1, 20)),
                               eos_token=12 if i % 2 else None))
    for _ in range(120):
        eng.tick()
    for f in futs:
        assert len(f.result(timeout=10)) >= 1
    assert eng.executables() == (n_exec, n_trace)
    assert eng.pool.allocated_bytes() == eng.pool.bytes()
    eng.close()


def test_capacity_grow_is_precompiled(model):
    eng = GenerateEngine(model, slots=2, page=16, factor=2.0,
                         max_len=64, prompt_buckets=(8,),
                         start=False, shed=False)
    eng.warmup()
    n_exec, n_trace = eng.executables()
    fut = eng.submit([2] * 8, max_new_tokens=50)  # crosses 16 and 32
    for _ in range(60):
        eng.tick()
    assert len(fut.result(timeout=10)) == 50
    assert eng.pool.capacity == 64 and eng.pool.stats()["grows"] == 2
    assert eng.executables() == (n_exec, n_trace)
    eng.close()


def test_continuous_refill_beats_drain(model):
    """Same tail-skewed workload, same slots, same executables: the
    continuous engine needs strictly fewer decode ticks (it refills
    freed slots mid-flight; drain waits on the longest member), and
    runs at strictly higher slot occupancy. Tick counts are scheduling
    facts — deterministic, unlike wall-clock."""
    wl = [([1, 2, 3], 4), ([4, 5], 24), ([6], 4), ([7, 8, 9], 4),
          ([2, 4], 4), ([3], 24), ([8], 4), ([9, 1], 4)]
    stats = {}
    for mode in ("continuous", "drain"):
        eng = GenerateEngine(model, slots=2, page=32, factor=2.0,
                             max_len=32, prompt_buckets=(4,),
                             queue_depth=32, refill=mode,
                             start=False, shed=False)
        futs = [eng.submit(p, max_new_tokens=n, eos_token=None)
                for p, n in wl]
        for _ in range(200):
            eng.tick()
        for f, (_, n) in zip(futs, wl):
            assert len(f.result(timeout=10)) == n
        stats[mode] = eng.stats()
        eng.close()
    assert stats["continuous"]["ticks"] < stats["drain"]["ticks"]
    assert (stats["continuous"]["avg_occupancy"]
            > stats["drain"]["avg_occupancy"])


def test_rejects_oversized_requests(model):
    eng = GenerateEngine(model, slots=1, page=16, max_len=32,
                         prompt_buckets=(8,), start=False, shed=False)
    with pytest.raises(ValueError):
        eng.make_request([1] * 9, max_new_tokens=4)     # past bucket
    with pytest.raises(ValueError):
        eng.make_request([1] * 8, max_new_tokens=25)    # past max_len
    with pytest.raises(ValueError):
        eng.make_request([], max_new_tokens=4)
    eng.close()


def test_queue_full_fast_reject(model):
    eng = GenerateEngine(model, slots=1, page=16, max_len=32,
                         prompt_buckets=(4,), queue_depth=2,
                         start=False, shed=False)
    for _ in range(2):
        eng.submit([1, 2], max_new_tokens=4)
    with pytest.raises(serving.QueueFullError):
        eng.submit([1, 2], max_new_tokens=4)
    eng.close(drain=False)


# ---------------------------------------------------------------------------
# ragged-prompt coalescing in the fixed-shape engine (satellite 2)


def test_seq_buckets_coalesce_ragged_prompts():
    """Requests whose sequence axes differ must land in ONE batch once
    the engine pads to a shared seq bucket BEFORE signature grouping —
    and scatter back bit-exact at their real lengths."""
    model = nn.ReLU()
    eng = serving.ServingEngine(
        inference.Predictor(model), buckets=[4], max_batch=4,
        timeout_ms=200.0, seq_buckets=(8, 16))
    xs = [np.random.RandomState(i).randn(1, n, 3).astype("f4")
          for i, n in enumerate((5, 7, 8, 3))]
    futs = [eng.submit(x) for x in xs]
    outs = [f.result(timeout=30) for f in futs]
    st = eng.stats()
    eng.close()
    for x, y in zip(xs, outs):
        assert y.shape == x.shape
        np.testing.assert_array_equal(y, np.maximum(x, 0.0))
    # all four ragged lengths coalesced into a single executed batch
    assert st["batches"] == 1


def test_seq_bucket_request_fields():
    eng = serving.ServingEngine(
        inference.Predictor(nn.ReLU()), buckets=[4], max_batch=4,
        timeout_ms=1.0, seq_buckets=(8, 16))
    req = eng.make_request((np.zeros((1, 5, 3), "f4"),), 1)
    assert req.seq_real == 5 and req.seq_padded == 8
    assert req.inputs[0].shape[1] == 8
    eng.close()


# ---------------------------------------------------------------------------
# KVCacheCell seeding


def test_kv_cache_cell_init_states_pads(model):
    cell = nnd.KVCacheCell(model.decode_fn, model.state, max_len=64)
    kv, _ = model.prefill_fn(model.state,
                             jnp.asarray([[1, 2, 3]], jnp.int32),
                             jnp.asarray([3], jnp.int32))
    padded, lengths = cell.init_states(kv, jnp.asarray([3], jnp.int32))
    for name, buf in padded.items():
        assert buf.shape[1] == 64
        np.testing.assert_array_equal(np.asarray(buf[:, :3]),
                                      np.asarray(kv[name]))
    assert int(lengths[0]) == 3


# ---------------------------------------------------------------------------
# decode metrics windows


def test_decode_metrics_window_fills_without_monitor():
    smetrics.reset_windows()
    for _ in range(3):
        smetrics.record_decode_tick(2, 4, 2, 1.5)
    smetrics.record_prefill(8, 2.0, 8)
    tps, p99 = smetrics.tokens_window()
    assert tps is not None and tps > 0
    assert p99 == 1.5
    roll = smetrics.decode_rollup()
    assert roll["tokens_per_s"] == tps
    assert roll["prefill_p50_ms"] == 2.0
    assert 0 < roll["prefill_ratio"] < 1
    smetrics.reset_windows()
    assert smetrics.tokens_window() == (None, None)


# ---------------------------------------------------------------------------
# decode-SLO supervisor scaling


def _two_replica_fleet(model):
    dev = jax.devices()[0]
    return MultiDecodeEngine(
        model, devices=[dev, dev], hedge_ms=0, supervise=False,
        initial_active=1, slots=2, page=16, factor=2.0, max_len=32,
        prompt_buckets=(4,), shed=False)


def test_tokens_floor_scale_up(model):
    smetrics.reset_windows()
    fleet = _two_replica_fleet(model)
    sup = ServingSupervisor(fleet, start=False, goodput_floor=0.0,
                            tokens_floor=10_000_000.0)
    try:
        futs = [fleet.submit([1, 2, 3], max_new_tokens=6)
                for _ in range(4)]
        for f in futs:
            f.result(timeout=30)
        assert fleet._active_count() == 1
        sup.tick(fleet)
        assert fleet._active_count() == 2
        d = sup.last_decision()
        assert d["decision"] == "scale_up"
        assert d["tokens_per_s"] < d["tokens_floor"]
    finally:
        sup.stop()
        fleet.close()
        smetrics.reset_windows()


def test_idle_engine_is_not_a_breach(model):
    """No decode traffic in the window -> tokens_per_s is None -> the
    supervisor must NOT scale up on a floor it can't even measure."""
    smetrics.reset_windows()
    fleet = _two_replica_fleet(model)
    sup = ServingSupervisor(fleet, start=False, goodput_floor=0.0,
                            tokens_floor=10_000_000.0)
    try:
        sup.tick(fleet)
        assert fleet._active_count() == 1
        d = sup.last_decision()
        assert d is None or d["decision"] != "scale_up"
    finally:
        sup.stop()
        fleet.close()
