"""ISSUE 45: a suffix that brings new ids or pairs grows the index.

``SweepBuilder.repin`` inserts the suffix's new vertex ids and (on a
preseeded builder) new pairs into the dense dictionaries under a
monotone remap and carries the fold state across (``"grown"``), where
it used to say ``"rebuild"``: the grown builder, and the
``GlobalTables`` over it, are a fresh build's bit for bit, before and
after both advance; growth rebinds and never writes an array a fork may
hold; and a standing engine (``_HopBatched.repin``, a Live
subscription's) follows in place, so no epoch after the first is a
``rebase``."""

import threading

import numpy as np
import pytest

from raphtory_tpu.algorithms import ConnectedComponents, PageRank
from raphtory_tpu.core import sweep as cs
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.core.sweep import SweepBuilder
from raphtory_tpu.engine import device_sweep as ds
from raphtory_tpu.engine.device_sweep import GlobalTables
from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                          HopBatchedPageRank)
from raphtory_tpu.ingestion.watermark import WatermarkRegistry
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import AnalysisManager, LiveQuery, ViewQuery
from raphtory_tpu.obs.freshness import FRESH
from raphtory_tpu.obs.trace import TRACER

from test_hopbatch import (_assert_columns_match_per_view, _close,
                           _same_component)

BUILDER_ATTRS = (cs._LOG_DERIVED + cs._STATE_COPIED + cs._STATE_SHARED
                 + cs._PAIR_TABLES + ("t_prev",))
#: the ids a base log knows: negative and positive, with room below,
#: between and above them
KNOWN = np.array([-900, -40, -7, 3, 12, 50, 51, 52, 400, 9000, 10**12])


@pytest.fixture(autouse=True)
def fold_for_real(monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    monkeypatch.setenv("RTPU_BATCH_WINDOW_MS", "0")    # no coalescing


def _assert_same_builder(got, want, what):
    for k in BUILDER_ATTRS:
        g, w = getattr(got, k), getattr(want, k)
        if k == "log":
            assert (g.n, g.compactions) == (w.n, w.compactions), what
        elif isinstance(w, np.ndarray):
            assert isinstance(g, np.ndarray) and g.dtype == w.dtype, (what, k)
            np.testing.assert_array_equal(g, w, err_msg=f"{what}: {k}")
        else:
            assert g == w and type(g) is type(w), (what, k, g, w)


def _assert_same_tables(got, want, what):
    assert set(vars(got)) == set(vars(want))
    for k, w in vars(want).items():
        g = getattr(got, k)
        if isinstance(w, np.ndarray):
            assert g.dtype == w.dtype, (what, k)
            np.testing.assert_array_equal(g, w, err_msg=f"{what}: {k}")
        else:
            assert g == w, (what, k, g, w)


def _events(log, rng, ids, t_lo, t_hi, n, mix=(0.3, 0.1, 0.4, 0.2)):
    """``n`` events over ``ids`` with times in ``[t_lo, t_hi)``: vertex
    adds and deletes, edge adds and deletes — a delete as likely as not
    of an id or pair nothing ever added."""
    for _ in range(n):
        kind = rng.choice(4, p=mix)
        t = int(rng.integers(t_lo, t_hi))
        a, b = (int(x) for x in rng.choice(ids, 2))
        if kind == 0:
            log.add_vertex(t, a)
        elif kind == 1:
            log.delete_vertex(t, a)
        elif kind == 2:
            log.add_edge(t, a, b)
        else:
            log.delete_edge(t, a, b)


def _base_log(rng):
    log = EventLog()
    _events(log, rng, KNOWN, 0, 50, 260)
    return log


# the suffixes of (a): each draws its ids so as to land where it says
def _ids_below(rng):
    return np.concatenate([KNOWN[:4], [-10**9, -5000, -901]])


def _ids_between(rng):
    return np.concatenate([KNOWN[2:8], [-8, 4, 13, 49, 53, 399, 401]])


def _ids_above(rng):
    return np.concatenate([KNOWN[-4:], [10**12 + 1, 2**62]])


def _ids_everywhere(rng):
    return np.concatenate([KNOWN, rng.integers(-10**6, 10**6, 9)])


SUFFIXES = {
    "new_ids_below": (_ids_below, (0.3, 0.1, 0.4, 0.2)),
    "new_ids_between": (_ids_between, (0.3, 0.1, 0.4, 0.2)),
    "new_ids_above": (_ids_above, (0.3, 0.1, 0.4, 0.2)),
    "new_ids_everywhere": (_ids_everywhere, (0.3, 0.1, 0.4, 0.2)),
    # the known ids alone: what is new is a pair between two of them
    "new_pairs_between_known_ids": (lambda rng: KNOWN, (0, 0, 0.7, 0.3)),
    # nothing was ever added: every event deletes, mostly the unknown
    "deletes_of_what_was_never_added": (_ids_everywhere, (0, 0.4, 0, 0.6)),
    "vertex_events_only": (_ids_everywhere, (0.7, 0.3, 0, 0)),
}
BUILDERS = {
    # the engines' builder (``LogIndex.prototype``): pairs preseeded
    "preseeded": dict(track_rows=False, preseed_pairs=True),
    # the view builder: the pair tables are fold state
    "views": dict(track_rows=True, preseed_pairs=False),
}


@pytest.mark.parametrize("advanced", [False, True],
                         ids=["pristine", "advanced"])
@pytest.mark.parametrize("builder", sorted(BUILDERS))
@pytest.mark.parametrize("suffix", sorted(SUFFIXES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_grown_builder_and_tables_are_a_fresh_builds(seed, suffix, builder,
                                                     advanced):
    rng = np.random.default_rng([seed, len(suffix)])
    draw, mix = SUFFIXES[suffix]
    kw = BUILDERS[builder]
    log = _base_log(rng)
    sw = SweepBuilder(log, **kw)
    tables = GlobalTables(sw)
    hops = []                       # the fresh build advances as sw did
    if advanced:
        hops += [30, 55]
        for T in hops:
            sw._advance(T)
    t_lo, statuses = 56, []
    for _ in range(2):              # two growths in a row
        _events(log, rng, draw(rng), t_lo, t_lo + 12, 40, mix)
        statuses.append(sw.repin(log))
        fresh = SweepBuilder(log, **kw)
        for T in hops:
            fresh._advance(T)
        what = f"{suffix} {builder} {statuses}"
        _assert_same_builder(sw, fresh, what + " before the advance")
        if statuses[-1] == "grown":
            assert sw.last_delta is None
        if builder == "preseeded":      # the builder the tables are over
            if statuses[-1] == "grown":
                tables = GlobalTables(sw)
            _assert_same_tables(tables, GlobalTables(fresh), what)
        hops.append(t_lo + 6)       # half the suffix, the rest next time
        sw._advance(hops[-1])
        fresh._advance(hops[-1])
        _assert_same_builder(sw, fresh, what + " after the advance")
        if kw["track_rows"]:
            from test_sweep import assert_views_equal
            from raphtory_tpu.core.snapshot import build_view
            assert_views_equal(sw.view_at(hops[-1]),
                               build_view(log, hops[-1]))
        t_lo = hops[-1] + 1
    # every one of these suffixes grows a preseeded builder; the view
    # builder's dictionary is its ids alone
    if builder == "preseeded" or suffix != "new_pairs_between_known_ids":
        assert "grown" in statuses, statuses
    assert set(statuses) <= {"grown", "extended"}


def test_a_new_pair_is_owed_its_endpoints_earlier_deletes():
    """A preseeded pair takes a dead mark from every delete of an
    endpoint, from the log's first event on (the killList join); a pair
    that joins the table at a growth gets the marks it missed."""
    log = EventLog()
    for t, (a, b) in enumerate([(1, 2), (2, 3), (3, 4)], start=1):
        log.add_edge(t, a, b)
    log.delete_vertex(5, 1)
    log.delete_vertex(7, 1)
    log.delete_vertex(8, 4)
    kw = BUILDERS["preseeded"]
    sw = SweepBuilder(log, **kw)
    sw._advance(6)
    sw._advance(9)
    log.add_edge(12, 1, 4)          # both ids known, both deleted before
    log.add_edge(13, 4, 99)         # one known and deleted, one new
    assert sw.repin(log) == "grown"
    fresh = SweepBuilder(log, **kw)
    fresh._advance(6)
    fresh._advance(9)
    _assert_same_builder(sw, fresh, "at t_prev")
    at = np.searchsorted(sw.e_enc, sw._pack(*sw._dense(np.array([[1], [4]]))))
    assert (sw.e_lat[at], sw.e_first[at], sw.e_alive[at], sw.e_seen[at]) \
        == (8, 5, False, True)
    for T in (12, 20):
        sw._advance(T)
        fresh._advance(T)
        _assert_same_builder(sw, fresh, f"at {T}")


@pytest.mark.parametrize("mutate, want", [
    (lambda log: log.add_edge(3, 0, 77), "rebuild"),     # at or below t_prev
    (lambda log: log.compact_to(EventLog(), 0), "rebuild"),
    (lambda log: None, "noop"),
    (lambda log: log.add_edge(60, 0, 1), "extended"),
    (lambda log: log.add_edge(60, 0, 77), "grown"),
], ids=["late_arrival", "compaction", "nothing", "known_pair", "new_id"])
def test_the_other_causes_stay_what_they_were(mutate, want):
    log = EventLog()
    log.add_edge(1, 0, 1)
    log.add_edge(2, 1, 2)
    sw = SweepBuilder(log, **BUILDERS["preseeded"])
    sw._advance(50)
    mutate(log)
    sfx = sw.suffix(log)            # reading changes nothing
    assert len(sw._t) == 2 and len(sw.uv) == 3
    assert (sfx if isinstance(sfx, str)
            else "grown" if sfx.grows else "extended") == want
    assert sw.repin(log) == want


def test_a_pin_with_nothing_to_preseed_from_is_rebuilt_not_extended():
    """``preseed_pairs`` over a log with no edge event preseeds nothing;
    the log's first edges then are a rebuild, not a table without them."""
    log = EventLog()
    log.add_vertex(1, 5)
    log.add_vertex(2, 6)
    sw = SweepBuilder(log, **BUILDERS["preseeded"])
    assert not sw._preseeded
    log.add_edge(3, 5, 6)
    assert sw.repin(log) == "rebuild"
    assert SweepBuilder(log, **BUILDERS["preseeded"])._preseeded


# --------------------------------------------------- (b) rebind, never write


@pytest.mark.parametrize("builder", sorted(BUILDERS))
def test_a_fork_taken_before_a_growth_reads_the_arrays_it_had(builder):
    rng = np.random.default_rng(45)
    log = _base_log(rng)
    proto = SweepBuilder(log, **BUILDERS[builder])
    proto._advance(40)
    fork = proto.fork()
    cp = proto.checkpoint()
    tables = GlobalTables(proto)
    held = {k: getattr(fork, k) for k in BUILDER_ATTRS}
    copies = {k: v.copy() for k, v in held.items()
              if isinstance(v, np.ndarray)}
    t_held = {k: v.copy() for k, v in vars(tables).items()
              if isinstance(v, np.ndarray)}
    cp_held = {k: v.copy() for k, v in cp.state.items()}

    _events(log, rng, _ids_everywhere(rng), 56, 70, 60)
    assert proto.repin(log) == "grown"
    grown_tables = GlobalTables(proto)
    proto._advance(70)

    for k, v in held.items():
        assert getattr(fork, k) is v                # the fork's own still
        if isinstance(v, np.ndarray):
            np.testing.assert_array_equal(v, copies[k], err_msg=k)
            if len(v) and k not in ("_t", "_k", "_s", "_d"):
                # every array the growth touched is a NEW one (the log's
                # columns are views of one growing buffer)
                assert not np.shares_memory(v, getattr(proto, k)), k
    for k, v in t_held.items():
        np.testing.assert_array_equal(getattr(tables, k), v, err_msg=k)
        assert not np.shares_memory(getattr(tables, k),
                                    getattr(grown_tables, k)), k
    for k, v in cp_held.items():
        np.testing.assert_array_equal(cp.state[k], v, err_msg=k)
    # the fork goes on as a builder over the OLD pin does
    old = SweepBuilder(fork.log, **BUILDERS[builder])
    old._advance(40)
    fork._advance(49)
    old._advance(49)
    _assert_same_builder(fork, old, "the fork, advanced after the growth")
    # and a checkpoint of before the growth no longer seeds the grown one
    with pytest.raises(ValueError, match="incompatible"):
        proto.fork(cp)


# ------------------------------------------- (c) a standing engine follows


ENGINES = {
    "pagerank": (lambda log: HopBatchedPageRank(log, tol=1e-7, max_steps=20),
                 PageRank(max_steps=20, tol=1e-7), _close(2e-5)),
    "cc": (lambda log: HopBatchedCC(log, max_steps=60),
           ConnectedComponents(max_steps=60), _same_component),
}


@pytest.mark.parametrize("kind", sorted(ENGINES))
def test_a_standing_engine_follows_growths_in_place(kind):
    """Each epoch's suffix brings new ids: the engine's ``repin`` says
    ``grown``, the next run serves what a rebuilt engine serves, bit for
    bit (a cold solve, the same shapes), and what ``build_view`` has."""
    make, program, agree = ENGINES[kind]
    rng = np.random.default_rng(7)
    log = _base_log(rng)
    hb = make(log)
    hb.run([55], [None, 20])
    t_lo, grown0 = 56, ds.log_index_status()["grown"]
    for epoch in range(3):
        _events(log, rng, _ids_everywhere(rng), t_lo, t_lo + 10, 50)
        tables = hb.tables
        assert hb.repin() == "grown"
        assert hb.tables is not tables and hb._dev_base is None
        np.testing.assert_array_equal(hb.tables.uv, hb.sw.uv)
        # the engine grew its own; the log's index was not asked, and the
        # next engine's lookup grows it by the same suffix: that one counts
        assert ds.log_index_status()["grown"] - grown0 == epoch
        rebuilt = make(log)
        assert rebuilt.index_status == "extended"
        assert ds.log_index_status()["grown"] - grown0 == epoch + 1
        _assert_same_tables(hb.tables, rebuilt.tables, f"epoch {epoch}")
        T = t_lo + 9
        got, _ = hb.run([T], [None, 20])
        want, _ = rebuilt.run([T], [None, 20])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        _assert_columns_match_per_view(hb, got, program, log, [T],
                                       [None, 20], agree)
        assert hb._dev_base is not None         # resident again
        t_lo = T + 1


def test_bfs_seeds_move_with_a_growth():
    log = EventLog()
    for t, (a, b) in enumerate([(10, 20), (20, 30), (30, 40)], start=1):
        log.add_edge(t, a, b)
    hb = HopBatchedBFS(log, seeds=(20,), max_steps=10)
    hb.run([5], [None])
    log.add_edge(6, 5, 10)          # 5 sorts below every id: ranks move
    log.add_edge(7, 40, 45)
    assert hb.repin() == "grown"
    got, _ = hb.run([9], [None])
    want, _ = HopBatchedBFS(log.freeze(), seeds=(20,),
                            max_steps=10).run([9], [None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    dist = dict(zip(hb.tables.uv.tolist(), np.asarray(got)[0].tolist()))
    assert dist[20] == 0 and dist[5] == 2 and dist[45] == 3


def _live_rows(mgr, name, log, wm, feed):
    """A Live subscription's rows over ``log`` while ``feed[k]`` is
    appended once ``k`` epochs were served (so every epoch after the
    first finds one suffix), with the job's spans."""
    q = LiveQuery(repeat=50, event_time=True, max_runs=len(feed) + 1)
    # a name of its own: the freshness plane keys subscriptions by job id,
    # and a manager numbers its jobs from 0
    job = mgr.submit(registry.resolve(name), q,
                     job_id=f"{name}_growth_{id(log):x}_{log.n}")

    def feeder():
        for k, (events, hi) in enumerate(feed, start=1):
            while len(job.results) < k and not job.wait(0.01):
                pass
            events(log)
            wm.advance("s", hi)
        wm.finish("s")

    th = threading.Thread(target=feeder)
    th.start()
    try:
        assert job.wait(120), job.error
    finally:
        th.join(30)
    assert job.status == "done", (job.status, job.error)
    return job


def test_live_subscription_over_a_log_that_grows_by_new_ids_each_epoch():
    """No epoch after the first is a ``rebase``; every row is what a
    View over a copy of the log (an index built from nothing) serves;
    the epochs' growths are ``engine.build reason=growth`` spans, and
    ``log_index`` counts the lookup that finds the log grown."""
    was = TRACER.enabled
    TRACER.enable()
    try:
        rng = np.random.default_rng(45)
        wm = WatermarkRegistry()
        wm.register("s")
        log = _base_log(rng)            # times below 50
        wm.advance("s", 49)
        feed = [(lambda log, lo=lo: _events(
                    log, np.random.default_rng(lo),
                    np.concatenate([KNOWN, [lo * 1000 + i for i in range(9)]]),
                    lo, lo + 50, 60), lo + 49)
                for lo in (50, 100, 150)]
        mgr = AnalysisManager(TemporalGraph(log, watermarks=wm))
        before = ds.log_index_status()
        job = _live_rows(mgr, "ConnectedComponents", log, wm, feed)
        after = ds.log_index_status()
    finally:
        (TRACER.enable if was else TRACER.disable)()
    assert [r["time"] for r in job.results] == [49, 99, 149, 199]

    sub = FRESH.live_subscription_rows()[job.id]
    assert sub["modes"] == {"rebase": 1, "incremental": 3}
    # the standing engine grew its own dictionaries: the log's index saw
    # one lookup, the first epoch's, and the next request's finds three
    # epochs of new ids in one suffix
    assert after["grown"] - before["grown"] == 0
    assert after["misses"] - before["misses"] == 1
    assert HopBatchedCC(log, max_steps=60).index_status == "extended"
    assert ds.log_index_status()["grown"] - after["grown"] == 1
    spans = [e for e in TRACER.for_trace(job.trace_id) if e["ph"] == "X"]
    epochs = [s for s in spans if s["name"] == "live.epoch"]
    assert [s["args"]["mode"] for s in epochs] \
        == ["rebase"] + ["incremental"] * 3
    assert [s["args"].get("repin") for s in epochs] == [None] + ["grown"] * 3
    assert [s["args"]["warm"] for s in epochs] == [False] * 4   # cold solves
    # a growth is an ``engine.build`` of its own, its stages inside it
    builds = [s for s in spans if s["name"] == "engine.build"]
    assert [b["args"]["reason"] for b in builds] \
        == ["rebase"] + ["growth"] * 3
    for b in builds[1:]:
        inside = {s["name"]: s["args"] for s in spans
                  if s["parent"] == b["sid"]}
        # (no fold cache here, so no fingerprint to carry)
        assert set(inside) == {"index.ids", "index.pairs", "index.tables"}
        assert inside["index.ids"]["grow"] and inside["index.ids"]["new_ids"]
        assert inside["index.pairs"]["grow"] \
            and inside["index.pairs"]["new_pairs"]
        assert inside["index.tables"] == {"grow": True}
        assert b["args"]["n_pad"] >= inside["index.ids"]["ids"]
    # the epochs' folds are their suffixes', never the log from its start
    adv = [s["args"]["rows"] for s in spans if s["name"] == "fold.advance"]
    assert adv[0] == 260 and all(0 < r <= 60 for r in adv[1:]), adv

    # the oracle: a View over a COPY of the log, whose index is built
    # from nothing
    copy = EventLog()
    copy.append_batch(*(log.column(c)
                        for c in ("time", "kind", "src", "dst")))
    oracle = AnalysisManager(TemporalGraph(copy))
    for row in job.results:
        view = oracle.submit(registry.resolve("ConnectedComponents"),
                             ViewQuery(int(row["time"])))
        assert view.wait(120), view.error
        assert row["result"] == view.results[0]["result"], row["time"]


def test_a_growth_past_a_memory_guard_falls_back_to_the_resweep(monkeypatch):
    """The guards are asked again wherever the padded sizes can have
    changed: a grown engine over one is dropped as a rebase's would be,
    and the subscription goes on serving from the re-sweep."""
    from raphtory_tpu.jobs import live

    was = TRACER.enabled
    TRACER.enable()
    try:
        rng = np.random.default_rng(46)
        wm = WatermarkRegistry()
        wm.register("s")
        log = _base_log(rng)
        wm.advance("s", 49)

        def grow(log, lo):
            if lo == 50:            # the first build was under the guard
                monkeypatch.setattr(live, "MAX_HOST_COLUMN_BYTES", 0)
            _events(log, np.random.default_rng(lo),
                    np.concatenate([KNOWN, [lo * 1000 + i for i in range(9)]]),
                    lo, lo + 50, 60)

        feed = [(lambda log, lo=lo: grow(log, lo), lo + 49)
                for lo in (50, 100)]
        mgr = AnalysisManager(TemporalGraph(log, watermarks=wm))
        job = _live_rows(mgr, "ConnectedComponents", log, wm, feed)
    finally:
        (TRACER.enable if was else TRACER.disable)()
    assert [r["time"] for r in job.results] == [49, 99, 149]
    epochs = [e["args"] for e in TRACER.for_trace(job.trace_id)
              if e["ph"] == "X" and e["name"] == "live.epoch"]
    assert [(a["mode"], a.get("repin"), a.get("declined"))
            for a in epochs] \
        == [("rebase", None, None), ("resweep", "grown", "memory_guard"),
            ("resweep", None, None)]

    monkeypatch.undo()
    oracle = AnalysisManager(TemporalGraph(log))
    for row in job.results:
        view = oracle.submit(registry.resolve("ConnectedComponents"),
                             ViewQuery(int(row["time"])))
        assert view.wait(120), view.error
        assert row["result"] == view.results[0]["result"], row["time"]
