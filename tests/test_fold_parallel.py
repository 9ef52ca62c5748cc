"""Multicore fold engine: checkpoint/fork equivalence, parallel chunk
folds bit-identical to the serial SweepBuilder, deeper prefetch, and the
bounded cross-request fold cache."""

import threading

import numpy as np
import pytest

from raphtory_tpu.core import sweep as cs
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.core.sweep import (FoldCache, SweepBuilder, fold_cache,
                                     fold_workers, log_fingerprint,
                                     prefetch_map)

from test_sweep import assert_views_equal, random_log


def _payloads_equal(a, b):
    if a is None or b is None:
        return a is b
    if isinstance(a, np.ndarray):
        return isinstance(b, np.ndarray) and bool(np.array_equal(a, b))
    if isinstance(a, (list, tuple)):
        return (len(a) == len(b)
                and all(_payloads_equal(x, y) for x, y in zip(a, b)))
    return a == b


# ---------------------------------------------------------- fork/checkpoint


@pytest.mark.parametrize("seed", [0, 3, 8])
def test_fork_views_bit_identical_to_serial(seed):
    """A fork seeded mid-sweep (the parallel chunk fold's shape) emits
    views bit-identical to both build_view and a single serial
    SweepBuilder — deletes, tombstone joins and id reuse included."""
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_events=500, n_ids=14, t_span=60)
    times = [5, 12, 20, 31, 44, 59]
    serial = SweepBuilder(log)
    serial_views = [serial.view_at(t) for t in times]
    # chunked: one fork per chunk, seeded by a bulk advance to the
    # previous chunk's boundary — exactly what the fold workers do
    base = SweepBuilder(log)
    for lo, hi in ((0, 2), (2, 4), (4, 6)):
        fork = base.fork()
        if lo > 0:
            fork._advance(times[lo - 1])
        for j in range(lo, hi):
            got = fork.view_at(times[j])
            assert_views_equal(got, serial_views[j])
            assert_views_equal(got, build_view(log, times[j]))


def test_fork_from_checkpoint_and_independence():
    rng = np.random.default_rng(17)
    log = random_log(rng, n_events=400, n_ids=12, t_span=50)
    sw = SweepBuilder(log)
    sw.view_at(20)
    cp = sw.checkpoint()
    sw.view_at(45)   # original advances past the checkpoint
    fork = sw.fork(cp)
    assert fork.t_prev == 20
    # the fork resumes from the checkpoint, unaffected by the original
    assert_views_equal(fork.view_at(30), build_view(log, 30))
    # and the original was not disturbed by the fork's advance
    assert_views_equal(sw.view_at(49), build_view(log, 49))


def test_fork_out_of_order_views_fall_back():
    """A backward view_at on a fork takes the build_view fallback path —
    same contract as the serial builder."""
    rng = np.random.default_rng(23)
    log = random_log(rng, n_events=300, n_ids=10, t_span=40)
    fork = SweepBuilder(log).fork()
    fork.view_at(30)
    assert_views_equal(fork.view_at(10), build_view(log, 10))   # fallback
    assert_views_equal(fork.view_at(35), build_view(log, 35))


def test_fork_rejects_incompatible_checkpoint():
    log = random_log(np.random.default_rng(1), n_events=100)
    cp = SweepBuilder(log).checkpoint()
    other = SweepBuilder(log, include_occurrences=True)
    with pytest.raises(ValueError, match="incompatible"):
        other.fork(cp)


# ------------------------------------------------- parallel chunk folds


@pytest.mark.parametrize("seed", [2, 9])
@pytest.mark.parametrize("mode", ["delta", "host"])
def test_parallel_fold_payloads_bit_identical(monkeypatch, seed, mode):
    """Engine-level: the parallel fold's chunk payloads (delta AND
    host-column paths) are bit-identical to the serial fold's, for
    adversarial logs with deletes and tombstones."""
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    monkeypatch.setenv("RTPU_FOLD", mode)
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    log = random_log(np.random.default_rng(seed), n_events=900, n_ids=40,
                     t_span=1000)
    hops = [150, 300, 450, 600, 750, 900]
    for chunks in (1, 2, 3):
        monkeypatch.setenv("RTPU_FOLD_WORKERS", "1")
        g1, p1 = HopBatchedPageRank(log).fold_payloads(hops, chunks=chunks)
        monkeypatch.setenv("RTPU_FOLD_WORKERS", "3")
        g2, p2 = HopBatchedPageRank(log).fold_payloads(hops, chunks=chunks)
        assert g1 == g2
        assert _payloads_equal(p1, p2), f"chunks={chunks}"


@pytest.mark.parametrize("warm", [False, True],
                         ids=["cold_cache", "warm_checkpoint"])
@pytest.mark.parametrize("workers", [1, 2, 4])
@pytest.mark.parametrize("seed", [5, 11, 21])
def test_host_column_entry_bit_identical_to_fold_columns(monkeypatch, seed,
                                                         workers, warm):
    """``fold_payloads(delta=False, hop_callback=...)`` — the mesh Range
    route's fold — hands out the serial ``_fold_columns``' columns bit
    for bit: inline at one worker, on forked units above, from the log's
    first event or seeded from an earlier request's checkpoint; and the
    callback sees every hop's fold state once."""
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    monkeypatch.setenv("RTPU_FOLD", "delta")    # the entry overrides it
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "64")
    fold_cache().clear()
    log = random_log(np.random.default_rng(seed), n_events=900, n_ids=40,
                     t_span=1000)
    hops = [600, 700, 800, 900]
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "1")
    ref_shells = {}
    _, want = HopBatchedPageRank(log)._fold_columns(
        hops, lambda T, sw: ref_shells.__setitem__(
            T, (sw.v_lat.copy(), sw.v_alive.copy())))
    monkeypatch.setenv("RTPU_FOLD_WORKERS", str(workers))
    if warm:
        # an earlier request that ended where this one starts leaves its
        # units' checkpoints behind (none at one worker: the inline fold
        # never looks into the cache)
        HopBatchedPageRank(log).fold_payloads([300, 400, 500, 600],
                                              delta=False)
        assert (fold_cache().stats()["entries"] > 0) == (workers > 1)
    hits0 = fold_cache().stats()["hits"]
    shells = {}
    hb = HopBatchedPageRank(log)
    groups, (got,) = hb.fold_payloads(
        hops, delta=False, hop_callback=lambda T, sw: shells.__setitem__(
            T, (sw.v_lat.copy(), sw.v_alive.copy())))
    assert groups == [hops]
    assert _payloads_equal(tuple(got), tuple(want))
    assert sorted(shells) == hops
    assert _payloads_equal([shells[T] for T in hops],
                           [ref_shells[T] for T in hops])
    assert hb.sw.t_prev == hops[-1]     # the engine's clock moved on
    # the job thread's part is a wait or an inline fold, never both
    if workers == 1:
        assert hb.fold_stall_seconds == 0.0
        assert hb.fold_inline_seconds == hb.fold_seconds > 0.0
    else:
        assert hb.fold_inline_seconds == 0.0 and hb.fold_seconds > 0.0
        # (cold, a unit may still find a faster sibling's checkpoint)
        assert fold_cache().stats()["hits"] > hits0 or not warm


def test_parallel_run_matches_serial_and_reuses(monkeypatch):
    """run() under parallel folds matches RTPU_FOLD_WORKERS=1 bitwise,
    and the engine stays reusable for a follow-on batch."""
    from raphtory_tpu.engine.hopbatch import HopBatchedCC

    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    log = random_log(np.random.default_rng(31), n_events=900, n_ids=40,
                     t_span=1000)
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "1")
    r1, _ = HopBatchedCC(log, max_steps=30).run(
        [200, 400, 600, 800], [300, None], chunks=2)
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "4")
    hb = HopBatchedCC(log, max_steps=30)
    r2, _ = hb.run([200, 400, 600, 800], [300, None], chunks=2)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    # follow-on batch on the same engine (adopted fork + rebuilt base)
    got, _ = hb.run([900, 1000], [300, None])
    fresh, _ = HopBatchedCC(log, max_steps=30).run([900, 1000],
                                                   [300, None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fresh))


def test_fold_workers_one_degrades_to_serial(monkeypatch):
    """RTPU_FOLD_WORKERS=1 must keep today's shared-builder pipeline —
    the parallel driver is never entered."""
    from raphtory_tpu.engine import hopbatch
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    monkeypatch.setenv("RTPU_FOLD_WORKERS", "1")
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    assert fold_workers() == 1

    def boom(*a, **k):
        raise AssertionError("parallel fold entered at workers=1")

    monkeypatch.setattr(hopbatch._HopBatched, "_fold_groups_parallel",
                        boom)
    log = random_log(np.random.default_rng(4), n_events=400, n_ids=20,
                     t_span=500)
    r, _ = HopBatchedPageRank(log, tol=0.0, max_steps=5).run(
        [200, 400], [None], chunks=2)
    assert np.asarray(r).shape[0] == 2


def test_device_sweep_parallel_matches_serial(monkeypatch):
    import jax

    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.engine.device_sweep import DeviceSweep

    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    log = random_log(np.random.default_rng(12), n_events=700, n_ids=30,
                     t_span=900)
    pr = PageRank(max_steps=8, tol=0.0)
    hops = [150, 300, 450, 600, 750]
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "1")
    r1, _ = DeviceSweep(log).run_sweep(pr, hops, windows=[200, None])
    monkeypatch.setenv("RTPU_FOLD_WORKERS", "3")
    ds = DeviceSweep(log)
    r2, _ = ds.run_sweep(pr, hops, windows=[200, None])
    for a, b in zip(jax.tree_util.tree_leaves(r1),
                    jax.tree_util.tree_leaves(r2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert ds.t_now == 750 and ds.sw.t_prev == 750
    with pytest.raises(ValueError, match="ascend"):
        ds.run_sweep(pr, [100, 200], windows=[None])


# ------------------------------------------------------- deeper prefetch


def test_prefetch_map_depth_orders_and_drains():
    done, bodies = [], []

    def make(i):
        def f():
            done.append(i)
            return i
        return f

    prefetch_map([make(i) for i in range(6)],
                 lambda p, s: bodies.append(p), depth=3)
    assert bodies == [0, 1, 2, 3, 4, 5]

    # an exploding body drains every in-flight fold before propagating
    started = []

    def slow(i):
        def f():
            started.append(i)
            return i
        return f

    with pytest.raises(RuntimeError, match="boom"):
        prefetch_map([slow(i) for i in range(5)],
                     lambda p, s: (_ for _ in ()).throw(
                         RuntimeError("boom")), depth=4)
    # everything submitted before the failure has completed (no zombie
    # folds mutating state after the caller's handler runs)
    assert started == sorted(started)


def test_prefetch_depth_knob(monkeypatch):
    monkeypatch.setenv("RTPU_PREFETCH_DEPTH", "5")
    assert cs.prefetch_depth() == 5
    monkeypatch.setenv("RTPU_PREFETCH_DEPTH", "0")
    assert cs.prefetch_depth() == 1


# ---------------------------------------------------------- fold cache


def test_fold_cache_bound_and_eviction_under_concurrency():
    """The byte bound holds at every moment under concurrent jobs, LRU
    entries evict (counted), and oversized values are refused."""
    cache = FoldCache(max_bytes=1 << 16)
    assert not cache.put(("big",), None, (1 << 16) + 1)
    errors = []

    def worker(w):
        try:
            for i in range(50):
                a = np.zeros(512, np.int64)   # 4 KiB
                assert cache.put(("p", w, i), [a], a.nbytes)
                cache.get(("p", w, (i * 7) % 50))
                st = cache.stats()
                assert st["bytes"] <= cache.max_bytes
        except Exception as e:   # surfaced below — threads swallow raises
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    st = cache.stats()
    assert st["bytes"] <= cache.max_bytes
    # 4 workers x 50 x 4KiB = 800 KiB through a 64 KiB bound: must evict
    assert st["evictions"] > 0
    assert st["entries"] <= (1 << 16) // 4096


def test_fold_cache_checkpoint_nearest():
    log = random_log(np.random.default_rng(2), n_events=300, n_ids=12,
                     t_span=50)
    sw = SweepBuilder(log, track_rows=False)
    fp = log_fingerprint(sw.log)
    cache = FoldCache(max_bytes=1 << 24)
    for t in (10, 20, 30):
        f = sw.fork()
        f._advance(t)
        assert cache.put_checkpoint(fp, f.checkpoint())
    assert cache.nearest_checkpoint(fp, sw._config(), 5) is None
    cp = cache.nearest_checkpoint(fp, sw._config(), 25)
    assert cp is not None and cp.t_prev == 20
    # a fork seeded from the cached checkpoint emits exact views
    fork = sw.fork(cp)
    fork._advance(40)
    st = SweepBuilder(log, track_rows=False)
    st._advance(40)
    np.testing.assert_array_equal(fork.e_lat, st.e_lat)
    np.testing.assert_array_equal(fork.v_alive, st.v_alive)


def test_fold_cache_hit_skips_folding_and_replays_shells(monkeypatch):
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "32")
    fold_cache().clear()
    log = random_log(np.random.default_rng(6), n_events=800, n_ids=30,
                     t_span=1000)
    hops = [200, 400, 600, 800]

    def run_with_shells(hb):
        shells = {}

        def cb(T, sw):
            shells[int(T)] = (sw.v_lat.copy(), sw.v_alive.copy(),
                              sw.v_first.copy())
        r, _ = hb.run(hops, [None], chunks=2, hop_callback=cb)
        return np.asarray(r), shells

    hb1 = HopBatchedPageRank(log, tol=0.0, max_steps=6)
    r1, s1 = run_with_shells(hb1)
    assert hb1.fold_seconds > 0
    hb2 = HopBatchedPageRank(log, tol=0.0, max_steps=6)
    r2, s2 = run_with_shells(hb2)
    assert hb2.fold_seconds == 0.0          # served from the cache
    np.testing.assert_array_equal(r1, r2)
    assert sorted(s1) == sorted(s2) == sorted(int(t) for t in hops)
    for t in s1:
        for a, b in zip(s1[t], s2[t]):
            np.testing.assert_array_equal(a, b)


def test_engine_reuse_after_cache_hit_stays_correct(monkeypatch):
    """A cache hit advances the DEVICE base but not the engine's host
    fold clock — residency must drop so a later overlapping batch cannot
    scatter an older catch-up delta onto the newer device state (review
    regression)."""
    from raphtory_tpu.engine.hopbatch import HopBatchedCC

    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "32")
    fold_cache().clear()
    log = random_log(np.random.default_rng(51), n_events=900, n_ids=35,
                     t_span=1000)
    # a FRESH engine populates the cache for grid [900, 1000]
    HopBatchedCC(log, max_steps=30).run([900, 1000], [None])
    hb = HopBatchedCC(log, max_steps=30)
    hb.run([600, 800], [None])                 # resident at 800
    hb.run([900, 1000], [None])                # cache HIT: device at 1000
    assert hb._dev_base is None                # residency dropped
    assert hb.sw.t_prev == 800                 # host clock never moved
    got, _ = hb.run([850, 950], [300, None])   # overlaps the cached grid
    fresh, _ = HopBatchedCC(log, max_steps=30).run([850, 950],
                                                   [300, None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fresh))


def test_fold_cache_disabled(monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    assert fold_cache() is None


def test_log_fingerprint_content_addressed():
    a = random_log(np.random.default_rng(5), n_events=200)
    b = random_log(np.random.default_rng(5), n_events=200)
    c = random_log(np.random.default_rng(6), n_events=200)
    assert log_fingerprint(a.pin()) == log_fingerprint(b.pin())
    assert log_fingerprint(a.pin()) != log_fingerprint(c.pin())


def test_repeated_range_job_hits_fold_cache(monkeypatch):
    """The serving story: two identical REST-shaped Range jobs — the
    second serves its fold from the cross-request cache."""
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery
    from raphtory_tpu.jobs.registry import resolve

    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "64")
    fold_cache().clear()
    log = random_log(np.random.default_rng(41), n_events=800, n_ids=30,
                     t_span=1000)
    g = TemporalGraph(log)
    mgr = AnalysisManager(g)
    q = RangeQuery(start=200, end=800, jump=200, window=400)

    def run_job():
        job = mgr.submit(resolve("PageRank"), q)
        assert job.wait(300) and job.status == "done", job.error
        return job.results

    r1 = run_job()
    before = fold_cache().stats()
    r2 = run_job()
    after = fold_cache().stats()
    assert after["hits"] > before["hits"]
    assert [row["result"] for row in r1] == [row["result"] for row in r2]


def test_fold_cache_locks_clean_under_sanitizer(monkeypatch):
    """The fold cache's lock (created after install, so tracked) stays
    cycle-free under concurrent payload/checkpoint traffic mixed with a
    parallel engine fold — the RTPU_SANITIZE=1 tier-1 job must stay
    clean."""
    from raphtory_tpu.analysis.sanitizer import LockSanitizer
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    monkeypatch.setenv("RTPU_FOLD_WORKERS", "3")
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    log = random_log(np.random.default_rng(19), n_events=500, n_ids=20,
                     t_span=600)
    san = LockSanitizer().install(patch_jax=False)
    try:
        cache = FoldCache(max_bytes=1 << 20)   # lock created tracked
        monkeypatch.setattr(cs, "_FOLD_CACHE", cache)
        monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "1")

        def churn(w):
            for i in range(20):
                a = np.zeros(256, np.int64)
                cache.put(("c", w, i), [a], a.nbytes)
                cache.get(("c", w, i - 1))

        threads = [threading.Thread(target=churn, args=(w,))
                   for w in range(3)]
        for t in threads:
            t.start()
        HopBatchedPageRank(log, tol=0.0, max_steps=4).run(
            [200, 400], [None], chunks=2)
        for t in threads:
            t.join()
        assert san.findings() == []
    finally:
        san.uninstall()


# ------------------------------------------------------------- metrics


def test_fold_metrics_exist():
    from prometheus_client import generate_latest

    from raphtory_tpu.obs.metrics import METRICS

    METRICS.fold_seconds.labels("parallel").observe(0.1)
    METRICS.fold_cache_hits.inc()
    METRICS.fold_cache_misses.inc()
    METRICS.fold_cache_evictions.inc()
    METRICS.fold_cache_bytes.set(123)
    text = generate_latest(METRICS.registry).decode()
    for name in ("raphtory_fold_seconds", "raphtory_fold_cache_hits_total",
                 "raphtory_fold_cache_misses_total",
                 "raphtory_fold_cache_evictions_total",
                 "raphtory_fold_cache_bytes"):
        assert name in text
