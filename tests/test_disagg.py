"""Disaggregated serving (PR 20): the export/import segment transport's
exact byte accounting, the PrefixCache's ref-counted LRU discipline,
bit-parity of the (prefill pool → priced handoff → decode pool)
topology against the single-engine oracle — greedy, sampled, prefix
hits, and mid-stream decode-replica drain — plus the reqtrace stage
waterfall (handoff_ms / prefix_lookup_ms / prefix_hit) reconciling.
All CPU, all fast."""
import numpy as np
import pytest

from paddle_tpu import monitor, serving
from paddle_tpu.serving import kv_cache, prefix_cache, reqtrace
from paddle_tpu.serving import metrics as smetrics
from paddle_tpu.serving.disagg import DisaggServer
from paddle_tpu.serving.generate import GenerateEngine
from paddle_tpu.serving.kv_cache import KVCachePool, bytes_per_token
from paddle_tpu.serving.prefix_cache import PrefixCache, prompt_key


@pytest.fixture(autouse=True)
def _clean():
    monitor.disable(flush_counters=False)
    reqtrace.reset()
    yield
    monitor.disable(flush_counters=False)
    reqtrace.reset()


@pytest.fixture(scope="module")
def model():
    return serving.demo_model(vocab=32, dim=16, heads=2, layers=2,
                              max_len=64, seed=1)


SPEC = {"k0": ((2, 4), "float32"), "v0": ((2, 4), "float32")}


def _segment(pad, length=None, fill=None):
    """A well-formed transport segment for SPEC."""
    length = pad if length is None else length
    rng = np.random.RandomState(0 if fill is None else fill)
    leaves = {name: rng.rand(pad, *tail).astype(np.float32)
              for name, (tail, _dt) in SPEC.items()}
    return {"length": length, "pad": pad,
            "bytes": sum(a.nbytes for a in leaves.values()),
            "leaves": leaves}


# ---------------------------------------------------------------------------
# export_slot / import_slot: the one segment transport (satellite c)


def test_export_import_roundtrip_exact_bytes():
    src = KVCachePool(SPEC, slots=2, page=32, factor=2.0, max_len=64)
    s = src.alloc()
    # land known content through the official import path, then read it
    # back out: the transport must be lossless and priced to the byte
    seg_in = _segment(16, length=10, fill=7)
    src.import_slot(s, seg_in)
    assert src.length(s) == 10

    before = src.allocated_bytes()
    seg = src.export_slot(s, pad_to=32)
    assert src.allocated_bytes() == before       # export never resizes
    assert seg["length"] == 10 and seg["pad"] == 32
    assert seg["bytes"] == bytes_per_token(SPEC) * 32
    for name, (tail, _dt) in SPEC.items():
        assert seg["leaves"][name].shape == (32, *tail)
        np.testing.assert_array_equal(seg["leaves"][name][:16],
                                      seg_in["leaves"][name])

    dst = KVCachePool(SPEC, slots=2, page=32, factor=2.0, max_len=64)
    d = dst.alloc()
    before = dst.allocated_bytes()
    got = dst.import_slot(d, seg)
    assert got == seg["bytes"]
    assert dst.allocated_bytes() == before       # import never resizes
    assert dst.length(d) == 10                   # ledger through note_length


def test_export_import_error_cases():
    pool = KVCachePool(SPEC, slots=1, page=16, factor=2.0, max_len=64)
    s = pool.alloc()
    pool.note_length(s, 12)
    with pytest.raises(ValueError, match="pad 8 < live length 12"):
        pool.export_slot(s, pad_to=8)
    with pytest.raises(ValueError, match="exceeds arena capacity"):
        pool.export_slot(s, pad_to=128)

    with pytest.raises(ValueError, match="exceeds arena capacity"):
        pool.import_slot(s, _segment(128))
    bad = _segment(16)
    bad["leaves"] = {"k0": bad["leaves"]["k0"]}         # missing v0
    with pytest.raises(ValueError, match="leaves"):
        pool.import_slot(s, bad)
    short = _segment(16)
    short["leaves"]["k0"] = short["leaves"]["k0"][:8]   # 8 rows, pad 16
    with pytest.raises(AssertionError, match="byte accounting"):
        pool.import_slot(s, short)


# ---------------------------------------------------------------------------
# PrefixCache: ref-counted LRU under a byte budget


def _seg_bytes(pad):
    return bytes_per_token(SPEC) * pad


def test_prefix_cache_hit_miss_and_refcount():
    cache = PrefixCache(SPEC, budget_bytes=_seg_bytes(16) * 4)
    prompt = [1, 2, 3]
    key, entry = cache.lookup(prompt)
    assert entry is None and key == prompt_key(prompt)
    assert cache.insert(key, _segment(16, length=3),
                        np.zeros(32, np.float32))
    key2, entry = cache.lookup(prompt)
    assert key2 == key and entry is not None
    assert entry.refs == 1 and entry.prompt_len == 3
    cache.release(key)
    assert cache.stats()["pinned"] == 0
    assert cache.hit_rate() == 0.5              # 1 hit / 2 lookups


def test_prefix_cache_key_is_length_salted():
    # a prompt that is a strict prefix of another must key differently
    assert prompt_key([1, 2, 3]) != prompt_key([1, 2, 3, 4])
    assert prompt_key([1, 2, 3]) == prompt_key(np.asarray([1, 2, 3]))


def test_prefix_cache_insert_asserts_spec_bytes():
    cache = PrefixCache(SPEC, budget_bytes=1 << 20)
    seg = _segment(16)
    seg["leaves"]["k0"] = seg["leaves"]["k0"][:8]
    with pytest.raises(AssertionError, match="spec-priced"):
        cache.insert("k", seg, np.zeros(32, np.float32))
    seg2 = _segment(16)
    seg2["bytes"] += 1
    with pytest.raises(AssertionError, match="self-reported"):
        cache.insert("k", seg2, np.zeros(32, np.float32))


def test_prefix_cache_lru_eviction_and_pinning():
    logits = np.zeros(32, np.float32)
    cache = PrefixCache(SPEC, budget_bytes=_seg_bytes(16) * 2)
    assert cache.insert("a", _segment(16), logits)
    assert cache.insert("b", _segment(16), logits)
    # LRU: "a" is oldest → evicted to make room for "c"
    assert cache.insert("c", _segment(16), logits)
    assert cache.stats()["evictions"] == 1
    assert "a" not in cache._entries
    assert "b" in cache._entries and "c" in cache._entries

    # pin "b" (a lookup takes a ref): "c" becomes the LRU victim
    cache._entries["b"].refs += 1
    assert cache.insert("d", _segment(16), logits)
    assert "b" in cache._entries and "c" not in cache._entries

    # everything pinned → insert refused, budget never broken
    cache._entries["d"].refs += 1
    assert not cache.insert("e", _segment(16), logits)
    assert cache.stats()["refused"] == 1
    assert cache.bytes() <= cache.budget_bytes


def test_prefix_cache_refuses_oversized_segment():
    cache = PrefixCache(SPEC, budget_bytes=_seg_bytes(16) - 1)
    assert not cache.insert("a", _segment(16), np.zeros(32, np.float32))
    assert len(cache) == 0


# ---------------------------------------------------------------------------
# topology bit-parity vs the single-engine oracle


def _oracle(model, jobs, **eng_kwargs):
    eng = GenerateEngine(model, start=False, **eng_kwargs)
    eng.warmup()
    futs = [eng.submit(p, max_new_tokens=n, sampling=sp, seed=seed)
            for p, n, sp, seed in jobs]
    for _ in range(2000):
        eng.tick()
        if all(f.done() for f in futs):
            break
    out = [[int(t) for t in f.result(timeout=5)] for f in futs]
    eng.close(drain=False)
    return out


def _disagg_execs(srv):
    return tuple(r.engine.executables()
                 for pool in (srv.prefill_pool, srv.decode_pool)
                 for r in pool._replicas)


def test_disagg_parity_greedy_and_sampled(model):
    monitor.enable()
    smetrics.reset_windows()
    sampled = {"temperature": 0.9, "top_k": 8}
    jobs = [([1, 2, 3], 8, None, None),
            ([5] * 20, 8, None, None),
            ([1, 2, 3], 8, sampled, 101),       # repeat → prefix hit
            ([1, 2, 3], 8, sampled, 202),       # repeat, different seed
            ([9, 8, 7, 6], 8, sampled, 303)]
    want = _oracle(model, jobs, slots=4, page=16, factor=2.0,
                   max_len=64, prompt_buckets=(8, 32))

    srv = DisaggServer(model, prefill_replicas=1, decode_replicas=1,
                       slots=4, page=16, factor=2.0, max_len=64,
                       prompt_buckets=(8, 32), supervise=False)
    srv.warmup()
    ex0 = _disagg_execs(srv)
    futs = [srv.submit(p, max_new_tokens=n, sampling=sp, seed=seed)
            for p, n, sp, seed in jobs]
    got = [[int(t) for t in f.result(timeout=30)] for f in futs]
    assert got == want                          # byte-for-byte streams

    # zero post-warmup compiles in BOTH pools — hits and handoffs land
    # on already-minted executables only
    assert _disagg_execs(srv) == ex0

    st = srv.stats()
    # repeats of [1,2,3] hit; each distinct prompt prefilled exactly once
    assert st["prefix"]["hits"] == 2
    assert st["prefix"]["misses"] == 3
    assert st["prefill"]["prefills"] == st["prefix"]["misses"]
    assert st["decode"]["prefills"] == 0        # decode pool never prefills
    assert st["decode"]["kv_imports"] == len(jobs)
    # every handoff priced exactly: per-token spec bytes × prompt bucket
    planned = sum(srv.planned_handoff_ms(len(p))[0]
                  for p, _n, _sp, _s in jobs)
    assert st["handoffs"] == len(jobs)
    assert st["handoff_bytes"] == planned
    srv.close()


def test_disagg_drain_midstream_parity(model):
    monitor.enable()
    smetrics.reset_windows()
    jobs = [([1, 2, 3], 40, {"temperature": 1.0, "top_k": 8}, 77),
            ([4, 5], 40, None, None)]
    want = _oracle(model, jobs, slots=4, page=16, factor=2.0,
                   max_len=64, prompt_buckets=(8, 32))

    srv = DisaggServer(model, prefill_replicas=1, decode_replicas=2,
                       slots=4, page=16, factor=2.0, max_len=64,
                       prompt_buckets=(8, 32), supervise=False)
    srv.warmup()
    futs = [srv.submit(p, max_new_tokens=n, sampling=sp, seed=seed)
            for p, n, sp, seed in jobs]
    # drain whichever decode replica seated work: its in-flight slots
    # export KV and resume mid-stream on the peer
    import time
    deadline = time.monotonic() + 10
    victim = None
    while victim is None and time.monotonic() < deadline:
        for r in srv.decode_pool._replicas:
            if r.engine.stats()["kv_imports"] > 0:
                victim = r
                break
        time.sleep(0.01)
    assert victim is not None
    srv.drain_decode_replica(victim.index, reason="test")
    got = [[int(t) for t in f.result(timeout=30)] for f in futs]
    assert got == want          # identical despite the mid-stream move
    srv.close()


def test_disagg_reqtrace_stages(model):
    monitor.enable()
    smetrics.reset_windows()
    reqtrace.reset()
    srv = DisaggServer(model, prefill_replicas=1, decode_replicas=1,
                       slots=4, page=16, factor=2.0, max_len=64,
                       prompt_buckets=(8, 32), supervise=False)
    srv.warmup()
    srv.run([1, 2, 3], max_new_tokens=6, timeout=30)   # miss
    srv.run([1, 2, 3], max_new_tokens=6, timeout=30)   # hit
    srv.close()

    recs = [r for r in reqtrace.recent() if r["outcome"] == "ok"]
    assert len(recs) == 2
    miss, hit = recs
    assert miss["prefix_hit"] is False and hit["prefix_hit"] is True
    for rec in recs:
        # the disagg stages appear and the waterfall still reconciles
        assert rec["prefix_lookup_ms"] >= 0.0
        assert rec["handoff_ms"] >= 0.0
        assert abs(rec["recon"] - 1.0) <= reqtrace.RECON_TOL
        assert rec["ttft_ms"] is not None
        assert any(h["hop"] == "handoff" for h in rec["hops"])
    assert "prefill_ms" in miss
    assert "prefill_ms" not in hit              # a hit never prefills


# -- use-after-donate: export racing a ticking engine -----------------------

def test_export_races_ticking_engine_no_use_after_donate(model):
    """The decode step DONATES the KV arena, and a drain thread exports
    slots off that arena while the tick thread is mid-dispatch. Every
    export must read live buffers: on a backend that honours donation
    (jax 0.9 does, also on CPU — and every TPU always did) a reader that
    catches ``pool.buffers`` between the donating call and its
    re-pointing dies with "Array has been deleted". Many iterations,
    because the window is a few microseconds per tick."""
    import threading
    import time
    # one capacity bucket: the arena never grows, so a fixed export pad
    # stays valid for the whole race
    eng = GenerateEngine(model, slots=4, page=64, factor=2.0, max_len=64,
                         prompt_buckets=(8, 32), kv_import=True)
    eng.warmup()
    stop = threading.Event()
    errors = []

    def feed():
        # keep every slot busy for the whole race
        k = 0
        while not stop.is_set():
            futs = [eng.submit([1 + (k + i) % 7, 2, 3], max_new_tokens=48)
                    for i in range(4)]
            k += 4
            for f in futs:
                try:
                    f.result(timeout=60)
                except Exception as e:  # noqa: BLE001
                    errors.append(e)
                    return

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()
    exports = 0
    try:
        deadline = time.monotonic() + 60
        while exports < 1500 and time.monotonic() < deadline:
            for s in range(eng.slots):
                seg = eng.pool.export_slot(s, pad_to=64)
                assert seg["pad"] == 64
                exports += 1
    finally:
        stop.set()
        feeder.join(timeout=60)
        eng.close()
    assert not errors, errors[:1]
    assert exports >= 1500 and eng.stats()["ticks"] > 50
