"""A fold checkpoint larger than the fold cache's whole bound is refused,
and the refusal is visible: ``FoldCache.stats()`` (so ``/statusz``
``fold_cache`` and the advisor), the ``fold.cache`` instant, the
``fold.checkpoint`` span. One that only just fits is stored and evicted
by the next insert (what the default bound was to a log of 2^23 events
while a checkpoint was charged the index's pair tables, until PR 34);
the span says that too (``stored``, ``nbytes``, ``seed="start"`` on the
next request). And the regime both make — every request folds the log
from its first event — serves the rows an ample cache serves, bit for
bit, inside the benchmark configuration's limits of the plain
reference. A bound that holds one checkpoint at what it OWNS plus a
request's payload, and would not hold it at the old charge, keeps the
newest checkpoint: the second request seeds from it (2^23 events under
the default bound, in small)."""

import argparse

import numpy as np
import pytest

import raphtory_tpu.core.sweep as cs
from raphtory_tpu.core.sweep import (FoldCache, SweepBuilder,
                                     log_fingerprint)
from raphtory_tpu.engine import device_sweep, hopbatch
from raphtory_tpu.obs.trace import TRACER

from benchmark import reference, run
from test_sweep import random_log

CELL = "twitter_wpr_big.range_windows"


@pytest.fixture
def traced():
    was = TRACER.enabled
    TRACER.enable()
    TRACER.clear()
    yield TRACER
    (TRACER.enable if was else TRACER.disable)()


def _checkpoint(t=30):
    log = random_log(np.random.default_rng(3), n_events=300, n_ids=12,
                     t_span=50)
    sw = SweepBuilder(log, track_rows=False)
    sw._advance(t)
    return log_fingerprint(sw.log), sw, sw.checkpoint()


def test_a_checkpoint_over_the_bound_is_refused_and_counted(traced):
    fp, sw, cp = _checkpoint()
    cache = FoldCache(max_bytes=cp.nbytes - 1)
    assert cache.stats()["refused"] == 0 == cache.stats()["refused_bytes"]
    assert cache.put_checkpoint(fp, cp) is False
    assert cache.put(("payload",), None, cp.nbytes + 7) is False
    st = cache.stats()
    assert (st["refused"], st["refused_bytes"]) == (2, cp.nbytes + 7)
    # a refusal stores and evicts nothing, and a later request finds none
    assert (st["entries"], st["bytes"], st["evictions"]) == (0, 0, 0)
    assert cache.nearest_checkpoint(fp, sw._config(), 10**9) is None
    said = [e["args"] for e in traced.recent(16)
            if e["name"] == "fold.cache" and e["args"].get("refused")]
    assert [(a["kind"], a["bytes"], a["hit"]) for a in said[-2:]] == [
        ("ckpt", cp.nbytes, False), ("payload", cp.nbytes + 7, False)]


def test_a_checkpoint_that_fits_is_stored_and_nothing_is_refused(traced):
    fp, sw, cp = _checkpoint()
    cache = FoldCache(max_bytes=cp.nbytes)          # exactly the bound
    assert cache.put_checkpoint(fp, cp) is True
    assert cache.nearest_checkpoint(fp, sw._config(), 40).t_prev == 30
    st = cache.stats()
    assert (st["refused"], st["refused_bytes"], st["entries"]) == (0, 0, 1)
    assert not any(e["args"].get("refused") for e in traced.recent(16)
                   if e["name"] == "fold.cache")


# ------------------------------------- served, at the rehearsal's size


def _serve_two_requests(monkeypatch, max_bytes, seed=2**31 + 33):
    """Two consecutive Range requests of the cell's traffic (the second
    starts where the first ended; batched month / week / day windows)
    through the harness's own node + REST, on a seeded R-MAT log at the
    rehearsal's size, with a fold cache of ``max_bytes``."""
    cache = FoldCache(max_bytes)
    # an env bound is whole megabytes, over a scale-10 checkpoint
    monkeypatch.setattr(cs, "fold_cache", lambda: cache)
    monkeypatch.setattr(hopbatch, "fold_cache", lambda: cache)
    loaded = run.load_cell(CELL)
    small = run.load_json(run.HERE, "rehearsal.json")
    loaded["config"] = run.merge(loaded["config"], small["config"])
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=2.0,
                              trace=0, rehearsal=False)
    r = run.Run(args, loaded, "cpu",
                {"platform": "cpu", "kind": "cpu", "count": 1})
    # no trace is taken here, and ``stop`` empties the trace directory:
    # not the one a traced rehearsal in another worker is reading
    r.trace_dir += ".untraced"
    try:
        r.boot()
        reqs = [r.loop.request(k) for k in (0, 1)]
        assert all(q["ok"] for q in reqs), [q["error"] for q in reqs]
        for q in reqs:
            q["ckpt"] = [s["args"] for s in r.rest.spans(q["trace_id"])
                         if s["name"] == "fold.checkpoint"]
        status = r.rest.get("/statusz")["fold_cache"]
        advice = r.rest.get("/advisez?cluster=0")["findings"]
        # beside the cache's account: the payload entries' bytes, and
        # the pair tables of the served log's index (the engines'
        # preseeded builder, ``/statusz`` ``log_index``)
        status["payload_bytes"] = [n for k, (_, n) in cache._entries.items()
                                   if k[0] != "ckpt"]
        proto = device_sweep._LOG_INDEXES[r.rt.graph.log].prototype
        status["pair_table_bytes"] = (proto.e_enc.nbytes
                                      + proto.e_enc_dst.nbytes)
    finally:
        r.stop()
    return r, reqs, status, advice


def _ranks(reqs):
    return np.array([[row["result"]["sum"]]
                     + [rank for _, rank in row["result"]["top10"]]
                     for q in reqs for row in q["rows"]])


def _refused(tracer):
    return [e["args"] for e in tracer.recent(4096)
            if e["name"] == "fold.cache" and e["args"].get("refused")]


def test_refused_or_evicted_checkpoints_serve_the_rows_an_ample_cache_serves(
        monkeypatch, traced, capsys):
    # --- a bound under one checkpoint: every one refused
    r, cold, st, advice = _serve_two_requests(monkeypatch, 64 << 10)
    spans = [a for q in cold for a in q["ckpt"]]
    assert spans and all(a["stored"] is False and a["nbytes"] > 64 << 10
                         for a in spans)
    # nothing kept, so the second request too starts at the first event
    assert {(a["seeded_from"], a["seed"]) for a in spans} == {(-1, "start")}
    # every checkpoint refused (and each request's fold payload, larger)
    said = _refused(traced)
    assert [a["bytes"] for a in said if a["kind"] == "ckpt"] \
        == [a["nbytes"] for a in spans]
    assert st["refused"] == len(said) and st["entries"] == 0
    assert st["refused_bytes"] == max(a["bytes"] for a in said)
    (f,) = [f for f in advice if f["rule_id"] == "fold-cache-refused"]
    assert str(st["refused_bytes"]) in f["summary"]
    nbytes = spans[0]["nbytes"]

    # --- a bound just over one checkpoint and under checkpoint +
    # payload: each is stored, then evicted by the next insert before
    # any request looks for it
    traced.clear()
    _, churn, st_c, _ = _serve_two_requests(monkeypatch, nbytes + nbytes // 50)
    spans_c = [a for q in churn for a in q["ckpt"]]
    assert len(spans_c) == len(spans)
    assert all(a["stored"] is True and a["nbytes"] == nbytes
               for a in spans_c)
    assert {(a["seeded_from"], a["seed"]) for a in spans_c} \
        == {(-1, "start")}
    assert not [a for a in _refused(traced) if a["kind"] == "ckpt"]
    assert st_c["hits"] == 0 and st_c["entries"] <= 1
    assert st_c["evictions"] >= len(spans_c) - 1

    # --- an ample bound: the second request seeds from the first's
    _, warm, st_w, advice_w = _serve_two_requests(monkeypatch, 64 << 20)
    first, second = (q["ckpt"] for q in warm)
    assert all(a["stored"] is True for a in first + second)
    assert {a["seed"] for a in first} == {"start"}
    assert {a["seed"] for a in second} == {"checkpoint"}
    # from the first request's newest (the second unit may find the
    # first unit's own instead, stored a moment before it looked)
    seeded = {a["seeded_from"] for a in second}
    assert min(seeded) == max(a["time"] for a in first)
    assert seeded <= {a["time"] for a in first + second}
    assert st_w["refused"] == 0 == st_w["evictions"]
    assert st_w["entries"] >= len(first)
    assert not [f for f in advice_w if f["rule_id"] == "fold-cache-refused"]

    # bit for bit: what is folded does not depend on what was cached
    for other in (churn, warm):
        assert [row["time"] for q in cold for row in q["rows"]] \
            == [row["time"] for q in other for row in q["rows"]]
        assert np.array_equal(_ranks(cold), _ranks(other))

    # and inside the configuration's limits of the plain reference
    cfg = r.cfg
    ref = reference.RefEvents(*r.columns, int(cfg["graph"]["id_space"]))
    rows = [row for q in cold for row in q["rows"]]
    assert len(rows) == 24
    for row in rows:
        got = r.algo.compare(
            row, r.algo.reference(*ref.fold(row["time"], row["windowsize"]),
                                  cfg["algorithm"]),
            cfg["correct"]["limits"], cfg["algorithm"])
        assert got["ok"], (row["time"], row["windowsize"], got)
    capsys.readouterr()         # the harness's phase lines


def test_a_bound_over_what_a_checkpoint_owns_keeps_the_newest_one(
        monkeypatch, traced, capsys):
    # sizes at the rehearsal's size, from an ample cache
    _, warm, st_w, _ = _serve_two_requests(monkeypatch, 64 << 20)
    (owned,) = {a["nbytes"] for q in warm for a in q["ckpt"]}
    payload = max(st_w["payload_bytes"])
    tables = st_w["pair_table_bytes"]
    assert 0 < payload < tables
    # a checkpoint's bytes are the fold state alone: two int64 and two
    # flags a pair and an id, where the two tables are 16 B a pair
    pairs = tables // 16
    assert owned > 18 * pairs and (owned - 18 * pairs) % 18 == 0
    # between owned + payload and the old charge, owned + the two
    # tables: charged those, every checkpoint here was refused (at 2^23
    # events and the default bound, 262.4 + 53.7 MB against 268.4, it
    # was stored and evicted); either way the next request found none.
    # Charged what it owns: 140.6 + 53.7 = 194.3 MB there
    bound = owned + payload + (tables - payload) // 4
    assert owned + payload < bound < owned + tables
    assert bound < 2 * owned        # two checkpoints never fit together
    traced.clear()
    _, kept, st, advice = _serve_two_requests(monkeypatch, bound)
    first, second = (q["ckpt"] for q in kept)
    assert first and second
    assert all(a["stored"] is True and a["nbytes"] == owned
               for a in first + second)
    assert min(first, key=lambda a: a["time"])["seed"] == "start"
    # the sibling's checkpoint evicted one of the first request's two;
    # the payload, put last, left the other: the second request found it
    # (its second unit may find the first unit's, stored a moment before)
    assert {a["seed"] for a in second} == {"checkpoint"}
    seeded = {a["seeded_from"] for a in second}
    assert min(seeded) in {a["time"] for a in first}
    assert seeded <= {a["time"] for a in first + second}
    assert st["hits"] > 0 and st["refused"] == 0 and st["evictions"] > 0
    assert st["bytes"] <= bound
    assert not _refused(traced)
    assert not [f for f in advice if f["rule_id"] == "fold-cache-refused"]
    # bit for bit the rows of the ample cache
    assert [row["time"] for q in kept for row in q["rows"]] \
        == [row["time"] for q in warm for row in q["rows"]]
    assert np.array_equal(_ranks(kept), _ranks(warm))
    capsys.readouterr()         # the harness's phase lines
