"""A fold checkpoint larger than the fold cache's whole bound is refused,
and the refusal is visible: ``FoldCache.stats()`` (so ``/statusz``
``fold_cache`` and the advisor), the ``fold.cache`` instant, the
``fold.checkpoint`` span. One that only just fits is stored and evicted
by the next insert (what the default bound is to a log of 2^23 events);
the span says that too (``stored``, ``nbytes``, ``seed="start"`` on the
next request). And the regime both make — every request folds the log
from its first event — serves the rows an ample cache serves, bit for
bit, inside the benchmark configuration's limits of the plain
reference."""

import argparse

import numpy as np
import pytest

import raphtory_tpu.core.sweep as cs
from raphtory_tpu.core.sweep import (FoldCache, SweepBuilder,
                                     log_fingerprint)
from raphtory_tpu.engine import hopbatch
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
    try:
        r.boot()
        reqs = [r.loop.request(k) for k in (0, 1)]
        assert all(q["ok"] for q in reqs), [q["error"] for q in reqs]
        for q in reqs:
            q["ckpt"] = [s["args"] for s in r.rest.spans(q["trace_id"])
                         if s["name"] == "fold.checkpoint"]
        status = r.rest.get("/statusz")["fold_cache"]
        advice = r.rest.get("/advisez?cluster=0")["findings"]
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

    # --- a bound just over one checkpoint (what 256 MB is to a log of
    # 2^23 events: 262,361,722 B of 268,435,456): each is stored, then
    # evicted by the next insert before any request looks for it
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
    assert {(a["seeded_from"], a["seed"]) for a in second} == {
        (max(a["time"] for a in first), "checkpoint")}
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
