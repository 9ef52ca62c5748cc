"""Live epoch engine (jobs/live.py + the engines' ``repin``): every
incrementally served epoch must be indistinguishable from a
from-scratch sweep at the same timestamp — CC/BFS bitwise, PageRank to
solver tolerance — on adversarial streams (deletes, tombstones,
out-of-order arrival), across residency loss and scheduled resyncs. The full re-sweep fallback is the oracle; these
tests ARE the equivalence gate."""

import threading

import numpy as np
import pytest

from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                          HopBatchedPageRank,
                                          HopBatchedSSSP)
from raphtory_tpu.ingestion.watermark import WatermarkRegistry
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import AnalysisManager, LiveQuery, ViewQuery
from raphtory_tpu.obs.freshness import FRESH

from test_sweep import random_log


@pytest.fixture(autouse=True)
def _fresh_reset():
    """The freshness registry is a process singleton and job ids restart
    per manager — clear between tests so subscription rows don't
    accumulate across collisions."""
    FRESH.clear()
    yield


N_IDS = 24


def _make_pool(rng, n_pairs=60):
    """The (src, dst) universe a stream draws from. The columnar
    engines preseed the pair table from the pinned log, so a suffix that
    is adopted with the dense space unchanged (``extended``) must reuse
    pairs the seed segment already introduced — a genuinely new pair
    GROWS the dictionaries (``tests/test_index_growth.py``)."""
    return [(int(a), int(b))
            for a, b in rng.integers(0, N_IDS, (n_pairs, 2))]


def _seed_log(rng, pool, t_span=40):
    """Initial segment: every vertex id and every pool pair exists (so
    later appends over the same universe extend the pin)."""
    log = EventLog()
    for v in range(N_IDS):
        log.add_vertex(0, v)
    for a, b in pool:
        log.add_edge(1, a, b)
    _append_segment(log, rng, pool, 1, t_span, n=200, deletes=True)
    return log


def _append_segment(log, rng, pool, t_lo, t_hi, n=120, deletes=False,
                    props=False):
    """Append ``n`` events with times in (t_lo, t_hi], ARRIVAL ORDER
    SHUFFLED (decoupled from event time) — ids and pairs stay inside
    the seeded universe so the suffix is adoptable."""
    times = rng.integers(t_lo + 1, t_hi + 1, n)
    for t in times:                        # rng order, not time order
        a, b = pool[int(rng.integers(0, len(pool)))]
        v = int(rng.integers(0, N_IDS))
        kind = int(rng.choice(4, p=[0.1, 0.1, 0.6, 0.2])) if deletes \
            else int(rng.choice([0, 2], p=[0.15, 0.85]))
        p = {"w": float(rng.integers(1, 5))} if props else None
        if kind == 0:
            log.add_vertex(int(t), v, p)
        elif kind == 1:
            log.delete_vertex(int(t), v)
        elif kind == 2:
            log.add_edge(int(t), a, b, p)
        else:
            log.delete_edge(int(t), a, b)
    return int(n)


ENGINES = {
    "pagerank": lambda log: HopBatchedPageRank(log, tol=1e-7,
                                               max_steps=30),
    "cc": lambda log: HopBatchedCC(log, max_steps=60),
    "bfs": lambda log: HopBatchedBFS(log, seeds=(0, 3), max_steps=60),
}


def _check(kind, got, want):
    got, want = np.asarray(got), np.asarray(want)
    if kind == "pagerank":
        np.testing.assert_allclose(got, want, atol=5e-5)
    else:                                   # CC labels / BFS distances
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("kind", list(ENGINES))
def test_epochs_match_scratch_on_adversarial_stream(kind):
    """Segmented adversarial stream: each epoch adopts the suffix
    (repin == extended), folds only the delta, and — where the monotone
    gate allows — warm-starts from the previous epoch's output. Every
    epoch must match a fresh engine built from scratch at the same t."""
    # distinct stream content per engine kind: the engines SHARE the
    # cross-request fold cache (payloads are engine-agnostic, keyed by
    # log content), and a cache hit replays another engine's payload —
    # which is correct, but makes per-epoch ship accounting reflect the
    # other param's fold strategy
    rng = np.random.default_rng({"pagerank": 7, "cc": 8, "bfs": 9}[kind])
    pool = _make_pool(rng)
    log = _seed_log(rng, pool)
    hb = ENGINES[kind](log)
    cuts = [40, 55, 70, 90]
    ranks, _ = hb.run([cuts[0]], [None])
    _check(kind, ranks, ENGINES[kind](log).run([cuts[0]], [None])[0])
    out_prev = np.asarray(ranks)
    base_ship = None
    for i in range(1, len(cuts)):
        # alternate add-only and delete-carrying segments: the warm
        # seed is only legal for CC/BFS on the add-only ones
        add_only = i % 2 == 1
        _append_segment(log, rng, pool, cuts[i - 1], cuts[i], n=80,
                        deletes=not add_only)
        assert hb.repin() == "extended"
        warm = out_prev if (kind == "pagerank" or add_only) else None
        ranks, _ = hb.run([cuts[i]], [None], warm_state=warm)
        inc_ship = hb.ship_bytes
        fresh = ENGINES[kind](log)
        want, _ = fresh.run([cuts[i]], [None])
        if base_ship is None:
            base_ship = fresh.ship_bytes
        _check(kind, ranks, want)
        out_prev = np.asarray(ranks)
        # O(Σdelta) ship: an 80-event epoch ships less than the fresh
        # engine's full base (masks + columns over the whole graph)
        assert inc_ship < base_ship, (inc_ship, base_ship)


def test_repin_grows_on_new_vertex_rebuilds_out_of_order_and_compaction():
    rng = np.random.default_rng(3)
    pool = _make_pool(rng)
    log = _seed_log(rng, pool)
    hb = HopBatchedCC(log, max_steps=60)
    hb.run([40], [None])
    # a vertex outside the pinned id space grows the dictionaries under
    # the standing engine (ISSUE 45): the next run is a fresh engine's
    log.add_edge(50, 0, N_IDS + 5)
    assert hb.repin() == "grown"
    got, _ = hb.run([55], [None])
    want, _ = HopBatchedCC(log, max_steps=60).run([55], [None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # weighted SSSP keeps pair positions in its weight stream: a rebuild
    sssp = HopBatchedSSSP(log, seeds=(0,), weight_prop="w", max_steps=60)
    sssp.run([55], [None])
    log.add_edge(60, 1, N_IDS + 6)
    assert sssp.repin() == "rebuild"

    rng2 = np.random.default_rng(4)
    log2 = _seed_log(rng2, _make_pool(rng2))
    hb2 = HopBatchedCC(log2, max_steps=60)
    hb2.run([40], [None])
    log2.add_edge(10, 1, 2)   # lands BEHIND the served epoch clock
    assert hb2.repin() == "rebuild"

    rng3 = np.random.default_rng(5)
    log3 = _seed_log(rng3, _make_pool(rng3))
    hb3 = HopBatchedCC(log3, max_steps=60)
    hb3.run([40], [None])
    log3.compact_to(EventLog(), 0)   # rewrite: row identities changed
    assert hb3.repin() == "rebuild"


def test_sssp_repin_extends_weight_stream():
    """Weighted SSSP: the sorted weight-update stream extends past the
    consumed cursor; incremental epochs stay bitwise equal to a fresh
    engine (weights fold identically from the same (time, row) order)."""
    rng = np.random.default_rng(11)
    pool = _make_pool(rng)
    log = _seed_log(rng, pool)
    _append_segment(log, rng, pool, 1, 40, n=120, props=True)
    hb = HopBatchedSSSP(log, seeds=(0,), weight_prop="w", max_steps=60)
    hb.run([40], [None])
    for lo, hi in [(40, 60), (60, 85)]:
        _append_segment(log, rng, pool, lo, hi, n=60, deletes=True,
                        props=True)
        assert hb.repin() == "extended"
        got, _ = hb.run([hi], [None])    # SSSP never takes a warm seed
        fresh = HopBatchedSSSP(log, seeds=(0,), weight_prop="w",
                               max_steps=60)
        want, _ = fresh.run([hi], [None])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_epoch_survives_residency_loss():
    """Mid-stream residency loss (the device-failure recovery path) must
    re-ship a consistent base — never serve from stale device state —
    and the epoch after it is resident again."""
    rng = np.random.default_rng(13)
    pool = _make_pool(rng)
    log = _seed_log(rng, pool)
    hb = HopBatchedCC(log, max_steps=60)
    hb.run([40], [None])
    _append_segment(log, rng, pool, 40, 55, n=60, deletes=True)
    assert hb.repin() == "extended"
    hb._drop_residency()                    # simulated device trouble
    got, _ = hb.run([55], [None])
    want, _ = HopBatchedCC(log, max_steps=60).run([55], [None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert hb._dev_base is not None

    _append_segment(log, rng, pool, 55, 70, n=60, deletes=True)
    assert hb.repin() == "extended"
    got, _ = hb.run([70], [None])
    want, _ = HopBatchedCC(log, max_steps=60).run([70], [None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------- jobs


def _adversarial_graph(seed=0, n=500, t_span=100):
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_events=n, n_ids=30, t_span=t_span)
    return TemporalGraph(log)


def _oracle(mgr, name, t, window=None):
    job = mgr.submit(registry.resolve(name), ViewQuery(int(t),
                                                       window=window))
    assert job.wait(120), job.error
    return job.results[0]["result"]


def test_live_event_time_epochs_match_view_oracle():
    """Event-time live CC over an adversarial (deletes, tombstones,
    out-of-order) log: every served epoch equals the one-shot ViewQuery
    at the same timestamp, bitwise — the acceptance equivalence gate."""
    g = _adversarial_graph(seed=21)
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=20, event_time=True, max_runs=4)
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)
    assert job.wait(120), job.error
    assert job.status == "done", (job.status, job.error)
    assert len(job.results) == 4
    for row in job.results:
        assert row["result"] == _oracle(
            mgr, "ConnectedComponents", row["time"]), row["time"]
    sub = FRESH.live_subscription_rows()[job.id]
    assert sub["epochs"] == 4
    assert sub["modes"].get("incremental", 0) >= 1, sub["modes"]


def test_live_pagerank_epochs_match_within_tolerance():
    g = _adversarial_graph(seed=22)
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=25, event_time=True, max_runs=3)
    job = mgr.submit(registry.resolve("PageRank"), q)
    assert job.wait(120), job.error
    assert job.status == "done", (job.status, job.error)
    for row in job.results:
        want = _oracle(mgr, "PageRank", row["time"])
        for k, v in row["result"].items():
            if isinstance(v, (int, float)):
                assert v == pytest.approx(want[k], abs=1e-4), k


def test_live_streaming_repin_between_epochs():
    """The jobs-layer repin path: the log GROWS between epochs (fenced
    by a live watermark), the standing engine adopts each suffix, and
    every epoch still matches the from-scratch oracle."""
    rng = np.random.default_rng(31)
    wm = WatermarkRegistry()
    wm.register("s")
    pool = _make_pool(rng)
    log = EventLog()
    for v in range(N_IDS):
        log.add_vertex(0, v)
    for a, b in pool:
        log.add_edge(1, a, b)
    _append_segment(log, rng, pool, 1, 99, n=250, deletes=True)
    wm.advance("s", 99)
    g = TemporalGraph(log, watermarks=wm)
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=50, event_time=True, max_runs=3)
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)

    def feed():
        for lo, hi in [(99, 160), (160, 210)]:
            _append_segment(log, rng, pool, lo, hi, n=70, deletes=True)
            wm.advance("s", hi)
        wm.finish("s")

    feeder = threading.Thread(target=feed)
    feeder.start()
    try:
        assert job.wait(120), job.error
    finally:
        feeder.join(30)
    assert job.status == "done", (job.status, job.error)
    assert [r["time"] for r in job.results] == [99, 149, 199]
    for row in job.results:
        assert row["result"] == _oracle(
            mgr, "ConnectedComponents", row["time"]), row["time"]
    sub = FRESH.live_subscription_rows()[job.id]
    assert sub["modes"].get("incremental", 0) >= 2, sub["modes"]
    assert sub["last_delta_rows"] > 0


def test_live_wall_clock_skips_unchanged_epochs():
    """Satellite 1: in wall-clock mode, when neither safe_time nor the
    log moved, the epoch is SKIPPED — no re-run of identical work, one
    emitted row, staleness still recorded per tick."""
    g = _adversarial_graph(seed=23)
    mgr = AnalysisManager(g)
    job = mgr.submit(registry.resolve("ConnectedComponents"),
                     LiveQuery(repeat=0.01, max_runs=5))
    assert job.wait(60), job.error
    assert len(job.results) == 1, len(job.results)
    sub = FRESH.live_subscription_rows()[job.id]
    assert sub["epochs"] == 5
    assert sub["modes"].get("skipped", 0) == 4, sub["modes"]
    # the subscription table rides /statusz and /freshz
    assert job.id in FRESH.status_block()["live_subscriptions"]
    assert job.id in FRESH.freshz()["live_subscriptions"]


def test_live_knob_off_restores_full_resweep(monkeypatch):
    """RTPU_LIVE=0 (the bench A/B off arm): every epoch full-re-sweeps
    through the legacy path, results identical to the oracle."""
    monkeypatch.setenv("RTPU_LIVE", "0")
    g = _adversarial_graph(seed=24)
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=30, event_time=True, max_runs=2)
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)
    assert job.wait(120), job.error
    assert job.status == "done", (job.status, job.error)
    sub = FRESH.live_subscription_rows()[job.id]
    assert sub["modes"] == {"resweep": 2}, sub["modes"]
    for row in job.results:
        assert row["result"] == _oracle(
            mgr, "ConnectedComponents", row["time"])


def test_live_resync_bounds_warm_drift(monkeypatch):
    """RTPU_LIVE_RESYNC=1: every second incremental epoch re-ships the
    base from exact host fold state (mode ``resync``) and solves cold —
    results still match the oracle."""
    monkeypatch.setenv("RTPU_LIVE_RESYNC", "1")
    g = _adversarial_graph(seed=25)
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=20, event_time=True, max_runs=4)
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)
    assert job.wait(120), job.error
    sub = FRESH.live_subscription_rows()[job.id]
    assert sub["modes"].get("resync", 0) >= 1, sub["modes"]
    for row in job.results:
        assert row["result"] == _oracle(
            mgr, "ConnectedComponents", row["time"])


def test_live_windowed_subscription_stays_exact():
    """Windowed aggregates advance by deltas (window masks recompute
    per epoch from fold state): windowed live == windowed view,
    exactly. Windows also disable the CC warm seed (non-monotone)."""
    g = _adversarial_graph(seed=26)
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=20, event_time=True, max_runs=3, window=30)
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)
    assert job.wait(120), job.error
    assert job.status == "done", (job.status, job.error)
    for row in job.results:
        assert row["result"] == _oracle(mgr, "ConnectedComponents",
                                        row["time"], window=30)


def test_live_epoch_feeds_admission_price_book():
    """Served epochs EWMA into the ``live:<alg>`` price key, and a
    LiveQuery admission estimate prefers it over the one-shot price."""
    g = _adversarial_graph(seed=27)
    mgr = AnalysisManager(g)
    q = LiveQuery(repeat=20, event_time=True, max_runs=3)
    job = mgr.submit(registry.resolve("PageRank"), q)
    assert job.wait(120), job.error
    sched = mgr.scheduler
    with sched._cond:
        per, n = sched._prices.get("live:PageRank", (None, 0))
    assert per is not None and n >= 1
    est = sched.price(registry.resolve("PageRank"),
                      LiveQuery(repeat=20, max_runs=1))
    assert est == pytest.approx(per * 1)


def test_registry_freezes_json_list_params():
    """REST params arrive as JSON lists; programs key compile caches by
    hash, so registry.resolve must freeze sequences — a weighted-SSSP
    live subscription with list seeds is exactly the request the live
    bench fleet submits."""
    prog = registry.resolve("SSSP", {"seeds": [0, 3], "weight_prop": "w"})
    assert prog.seeds == (0, 3)
    hash(prog)   # would raise TypeError on an unfrozen list

    g = _adversarial_graph(seed=28)
    mgr = AnalysisManager(g)
    job = mgr.submit(prog, ViewQuery(40))
    assert job.wait(120), job.error
    assert job.status == "done", (job.status, job.error)
