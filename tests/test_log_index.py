"""ISSUE 26: a log's index is built once, not once a request.

What a columnar engine derives from the log alone (the preseeded fold
builder, the global tables, the fold-cache fingerprint) is kept per live
log by ``engine/device_sweep.log_index`` and forked per engine. A fork is
what a fresh build is; validity is exact (``n``, ``compactions``; a
suffix's new ids and pairs grow the index: ISSUE 45,
``tests/test_index_growth.py``); two jobs arriving together build it once; it
dies with the log. An engine over a FROZEN log never uses the cache, so
``Engine(log.freeze())`` is this file's freshly built reference."""

import gc
import threading
import weakref

import numpy as np
import pytest

from raphtory_tpu.core import sweep as cs
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.engine import device_sweep as ds
from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                          HopBatchedPageRank,
                                          HopBatchedSSSP)
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery
from raphtory_tpu.jobs.rest import _statusz

from test_fold_parallel import _payloads_equal
from test_sweep import random_log

ENGINES = {
    "pagerank": lambda log: HopBatchedPageRank(log, tol=0, max_steps=20),
    "cc": lambda log: HopBatchedCC(log, max_steps=60),
    "bfs": lambda log: HopBatchedBFS(log, seeds=(0, 3), max_steps=60),
    "sssp_weighted": lambda log: HopBatchedSSSP(
        log, seeds=(0, 3), weight_prop="w", max_steps=60),
}
TABLE_ARRAYS = ("uv", "all_enc", "eng_of_rank", "e_src", "e_dst", "vids")
TABLE_SCALARS = ("n", "m", "n_pad", "m_pad", "tdtype", "tmin")
HOPS = [150, 300, 450, 600, 750, 900]


@pytest.fixture(autouse=True)
def fold_for_real(monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    monkeypatch.setenv("RTPU_BATCH_WINDOW_MS", "0")    # no coalescing


def _counts():
    c = ds.log_index_status()
    return np.array([c["hits"], c["extends"], c["misses"]])


def _grown():
    return ds.log_index_status()["grown"]


# ------------------------------------------------- a fork is a fresh build


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_engine_forked_from_a_hit_is_bitwise_a_fresh_one(kind):
    log = random_log(np.random.default_rng(26), n_events=900, n_ids=40,
                     t_span=1000, props=True)
    make = ENGINES[kind]
    fresh = make(log.freeze())
    assert fresh.index_status == "miss"
    assert make(log).index_status == "miss"      # builds and stores
    hit = make(log)
    assert hit.index_status == "hit"

    for name in TABLE_ARRAYS:
        np.testing.assert_array_equal(getattr(hit.tables, name),
                                      getattr(fresh.tables, name), name)
    for name in TABLE_SCALARS:
        assert getattr(hit.tables, name) == getattr(fresh.tables, name)
    assert hit.sw.t_prev is None and hit.sw._config() == fresh.sw._config()

    g_hit, p_hit = hit.fold_payloads(HOPS, chunks=2)
    g_new, p_new = fresh.fold_payloads(HOPS, chunks=2)
    assert g_hit == g_new
    assert _payloads_equal(p_hit, p_new)
    got, _ = make(log).run(HOPS[:3], [400, None])
    want, _ = make(log.freeze()).run(HOPS[:3], [400, None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------- validity is exact


def _base_log():
    """Ten ids, the pairs (i, i+1) and (i, i+2), one add each, times
    1..20, then deletes of two of them: small enough to reason about."""
    log = EventLog()
    t = 0
    for i in range(8):
        for j in (i + 1, i + 2):
            t += 1
            log.add_edge(t, i, j)
    log.delete_edge(30, 0, 1)
    log.delete_edge(31, 4, 6)
    return log


def _nothing(log):
    pass


def _suffix_existing_pairs(log):
    log.add_edge(60, 0, 1)
    log.delete_edge(61, 2, 3)
    log.add_vertex(62, 9)


def _suffix_new_id(log):
    log.add_edge(60, 0, 77)


def _suffix_new_pair(log):
    log.add_edge(60, 0, 9)      # both ids known, the pair is not


def _suffix_late_event(log):
    log.delete_edge(10, 2, 3)   # at or below the served t_prev (50)


def _compaction_same_rows(log):
    other = EventLog()
    for r in range(log.n):      # the same row count, another history
        other.add_edge(r + 1, r % 7, (r % 7) + 1)
    log.compact_to(other, since_row=log.n)


def _suffix_time_overflow(log):
    log.add_edge(1 << 31, 0, 1)  # past the int32 the tables narrowed to


#: mutation -> (the index's answer for the NEXT engine, the SERVING
#: engine's own repin). A late event is the one place they differ: the
#: engine that served t=50 must be rebuilt, while the index's pristine
#: builder (no t_prev) adopts the suffix and a fresh fork folds it all.
CASES = {
    "unchanged": (_nothing, "hit", "noop"),
    "suffix_among_existing_pairs": (_suffix_existing_pairs, "extended",
                                    "extended"),
    "suffix_with_a_new_id": (_suffix_new_id, "extended", "grown"),
    "suffix_with_a_new_pair": (_suffix_new_pair, "extended", "grown"),
    "event_at_or_below_a_served_t_prev": (_suffix_late_event, "extended",
                                          "rebuild"),
    "compaction_to_the_same_row_count": (_compaction_same_rows, "miss",
                                         "rebuild"),
    "time_that_overflows_the_narrowed_dtype": (_suffix_time_overflow,
                                               "miss", "rebuild"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_index_validity(case):
    mutate, want_index, want_repin = CASES[case]
    log = _base_log()
    served = HopBatchedCC(log, max_steps=60)
    assert served.index_status == "miss"
    served.run([50], [None])
    cs.log_fingerprint(served.sw.log)        # so an extension carries it
    n_before = log.n
    before, grown_before = _counts(), _grown()

    mutate(log)
    nxt = HopBatchedCC(log, max_steps=60)
    assert nxt.index_status == want_index
    assert (_counts() - before).tolist() == [
        int(want_index == s) for s in ("hit", "extended", "miss")]
    # a lookup whose suffix grew the dictionaries is an ``extended`` one,
    # counted as ``grown`` besides; the serving engine's own repin then
    # grows ITS builder and tables: no lookup, so the index counts none
    assert _grown() - grown_before == int(want_repin == "grown")
    assert served.repin() == want_repin
    assert _grown() - grown_before == int(want_repin == "grown")
    if want_repin == "grown":
        np.testing.assert_array_equal(served.tables.eng_of_rank,
                                      nxt.tables.eng_of_rank)
        got, _ = served.run([65], [None, 25])
        want, _ = HopBatchedCC(log.freeze(), max_steps=60).run(
            [20, 50, 65], [None, 25])
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(want)[4:])
    if case == "compaction_to_the_same_row_count":
        assert log.n == n_before

    # whatever the lookup said, the engine is a fresh build's equal
    fresh = HopBatchedCC(log.freeze(), max_steps=60)
    assert nxt.sw.log.n == log.n == fresh.sw.log.n
    np.testing.assert_array_equal(nxt.tables.all_enc, fresh.tables.all_enc)
    assert nxt.tables.tdtype == fresh.tables.tdtype
    hops = [20, 50, 65]
    got, _ = nxt.run(hops, [None, 25])
    want, _ = fresh.run(hops, [None, 25])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # and the fingerprint its pin carries is the content's
    assert cs.log_fingerprint(nxt.sw.log) == cs._fingerprint(log.freeze())


def test_extended_fingerprint_is_carried_not_recomputed(monkeypatch):
    log = _base_log()
    first = HopBatchedCC(log, max_steps=60)
    cs.log_fingerprint(first.sw.log)
    _suffix_existing_pairs(log)
    monkeypatch.setattr(cs, "_tracer", lambda: pytest.fail(
        "log_fingerprint went to compute: the pin carried none"))
    nxt = HopBatchedCC(log, max_steps=60)
    assert nxt.index_status == "extended"
    assert nxt.sw.log is not first.sw.log
    assert cs.log_fingerprint(nxt.sw.log)[0] == log.n


# ------------------------------------------------------------ jobs over it


def _range_rows(mgr, q):
    job = mgr.submit(registry.resolve("PageRank",
                                      {"max_steps": 20, "tol": 0}), q)
    assert job.wait(300)
    assert job.status == "done", job.error
    return [(r["time"], r["windowsize"], r["result"]) for r in job.results]


def test_two_range_jobs_together_build_the_index_once():
    log = random_log(np.random.default_rng(3), n_events=1500, n_ids=60,
                     t_span=1000)
    mgr = AnalysisManager(TemporalGraph(log))
    qs = [RangeQuery(start=300, end=600, jump=100, windows=(500, 100)),
          RangeQuery(start=500, end=900, jump=200, windows=(500, 100))]
    before = _counts()
    rows = [None, None]
    gate = threading.Barrier(2)

    def go(i):
        gate.wait()
        rows[i] = _range_rows(mgr, qs[i])

    threads = [threading.Thread(target=go, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(600)
    assert (_counts() - before).tolist() == [1, 0, 1]   # one build, one fork
    for i, q in enumerate(qs):
        assert rows[i] and rows[i] == _range_rows(mgr, q)   # serial rerun
    assert (_counts() - before).tolist() == [3, 0, 1]


def test_only_the_first_request_fingerprints_the_log(monkeypatch):
    """With the fold cache on, the O(events) content hash that keys it is
    computed on the index's pin: once for a run of requests."""
    from raphtory_tpu.obs.trace import TRACER

    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "64")
    was = TRACER.enabled
    TRACER.enable()
    try:
        log = random_log(np.random.default_rng(8), n_events=900, n_ids=40,
                         t_span=1000)
        mgr = AnalysisManager(TemporalGraph(log))
        seen = []
        for k in range(3):
            job = mgr.submit(
                registry.resolve("PageRank", {"max_steps": 20, "tol": 0}),
                RangeQuery(start=300 + 100 * k, end=500 + 100 * k,
                           jump=100, windows=(500,)))
            assert job.wait(300) and job.status == "done", job.error
            names = [e["name"] for e in TRACER.for_trace(job.trace_id)
                     if e["ph"] == "X"]
            seen.append((names.count("engine.build"),
                         names.count("fold.fingerprint")))
        assert seen == [(1, 1), (1, 0), (1, 0)]
    finally:
        (TRACER.enable if was else TRACER.disable)()


def test_a_request_back_in_time_after_a_later_one_still_serves():
    log = random_log(np.random.default_rng(4), n_events=900, n_ids=40,
                     t_span=1000)
    later, _ = HopBatchedCC(log, max_steps=60).run([800, 900], [300])
    back = HopBatchedCC(log, max_steps=60)
    assert back.index_status == "hit"
    got, _ = back.run([200, 250], [300])
    want, _ = HopBatchedCC(log.freeze(), max_steps=60).run([200, 250],
                                                         [300])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the index's own builder never moved
    assert ds._LOG_INDEXES[log].prototype.t_prev is None


def test_the_entry_dies_with_the_log_and_a_frozen_log_keeps_none():
    gc.collect()
    log = random_log(np.random.default_rng(5), n_events=400, n_ids=30,
                     t_span=500)
    held = ds.log_index_status()["bytes"]
    hb = HopBatchedCC(log, max_steps=60)
    own = ds._LOG_INDEXES[log].nbytes
    assert own > 0 and ds.log_index_status()["bytes"] == held + own
    # a frozen log is its own pin: an entry would keep its key alive
    frozen = log.freeze()
    for _ in range(2):
        assert HopBatchedCC(frozen, max_steps=60).index_status == "miss"
    assert frozen not in ds._LOG_INDEXES
    assert ds.log_index_status()["bytes"] == held + own

    refs = [weakref.ref(log), weakref.ref(hb.tables), weakref.ref(frozen)]
    del hb, log, frozen
    gc.collect()
    assert [r() for r in refs] == [None, None, None]
    assert ds.log_index_status()["bytes"] == held


def test_statusz_log_index_counts_match():
    log = random_log(np.random.default_rng(6), n_events=600, n_ids=30,
                     t_span=1000)
    mgr = AnalysisManager(TemporalGraph(log))
    q = RangeQuery(start=300, end=600, jump=100, windows=(500,))
    was = _statusz(mgr)["log_index"]
    assert set(was) == {"hits", "extends", "grown", "misses", "bytes",
                        "forks", "fork_copies", "fork_copied_bytes"}
    _range_rows(mgr, q)                 # miss
    _range_rows(mgr, q)                 # hit
    log.add_vertex(900, int(log.column("src")[0]))
    _range_rows(mgr, q)                 # extended: a known id, no pair
    log.add_edge(950, 10_001, 10_002)
    _range_rows(mgr, q)                 # extended, grown: new ids
    now = _statusz(mgr)["log_index"]
    assert [now[k] - was[k]
            for k in ("hits", "extends", "grown", "misses")] \
        == [1, 2, 1, 1]
    assert now["bytes"] - was["bytes"] == ds._LOG_INDEXES[log].nbytes > 0
