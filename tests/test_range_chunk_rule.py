"""ISSUE 43: the number of dispatches of a columnar Range follows what
the request is — whether warm-started chunks can save supersteps, and
whether one dispatch of all ``hops x windows`` columns stays on the
engine's fast path — not the hop count alone
(``jobs/manager._range_chunks``, ``_HopBatched.dispatch_columns_ok``)."""

from types import SimpleNamespace

import numpy as np
import pytest

from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                          HopBatchedCDLP, HopBatchedLCC,
                                          HopBatchedPageRank, HopBatchedSGC)
from raphtory_tpu.jobs.manager import (AnalysisManager, Job, RangeQuery,
                                       _range_chunks)

import test_stage_spans as served

# the served-request fixture of the stage-span tests, under its own name
traced = served.traced
_log, _named, _pagerank, _spans = (served._log, served._named,
                                   served._pagerank, served._spans)

M_RANGE = 3_735_552     # twitter_wpr / graph500_cdlp / graph500_lcc
M_BIG = 7_667_712       # twitter_wpr_big
M_SMALL = 8_192         # under 2^16 rows nothing tiles


def _engine(cls, m_pad, tol=None):
    """The two things the rule asks an engine, without a log behind it."""
    hb = object.__new__(cls)
    hb.tables = SimpleNamespace(m_pad=m_pad)
    if tol is not None:
        hb.tol = tol
    return hb


@pytest.mark.parametrize(
    "hops,windows,m_pad,budget_mb,tol,engine,want", [
        # the benchmark's cells
        (4, 3, M_RANGE, 256, 0, HopBatchedPageRank, (1, "one_dispatch")),
        (4, 3, M_BIG, 256, 0, HopBatchedPageRank, (2, "fit")),
        (2, 3, M_RANGE, 256, None, HopBatchedCDLP, (1, "one_dispatch")),
        (2, 3, M_RANGE, 256, None, HopBatchedLCC, (1, "one_dispatch")),
        # a PageRank that can halt early keeps the ladder and its warm start
        (4, 3, M_RANGE, 256, 1e-7, HopBatchedPageRank, (2, "warm_start")),
        (8, 3, M_RANGE, 256, 1e-7, HopBatchedPageRank, (4, "warm_start")),
        (2, 3, M_SMALL, 256, 1e-7, HopBatchedPageRank, (1, "warm_start")),
        # past ops/segment's 16 columns: the fewest chunks that fit, or
        # the ladder where none of 2, 3, 4 does
        (24, 3, M_SMALL, 256, 0, HopBatchedPageRank, (4, "ladder")),
        (24, 1, M_SMALL, 256, 0, HopBatchedPageRank, (2, "fit")),
        (12, 3, M_SMALL, 256, 0, HopBatchedPageRank, (3, "fit")),
        # the other kinds have no column limit, only the tile budget
        (24, 3, M_SMALL, 256, None, HopBatchedCC, (1, "one_dispatch")),
        (4, 3, M_RANGE, 256, None, HopBatchedBFS, (1, "one_dispatch")),
        (4, 3, 30_000_000, 256, None, HopBatchedCC, (2, "ladder")),
        # odd hop counts
        (5, 3, M_SMALL, 256, 0, HopBatchedPageRank, (1, "one_dispatch")),
        (7, 3, M_SMALL, 256, 0, HopBatchedPageRank, (1, "ladder")),
        (9, 3, M_SMALL, 256, 0, HopBatchedPageRank, (3, "fit")),
        # a budget under which C = 12 tiles, then C = 6, then C = 3
        (4, 3, M_RANGE, 128, 0, HopBatchedPageRank, (2, "fit")),
        (4, 3, M_RANGE, 64, 0, HopBatchedPageRank, (4, "fit")),
        (4, 3, M_RANGE, 32, 0, HopBatchedPageRank, (2, "ladder")),
    ])
def test_the_chunk_rule_as_a_table(monkeypatch, hops, windows, m_pad,
                                   budget_mb, tol, engine, want):
    monkeypatch.setenv("RTPU_TILE_BUDGET_MB", str(budget_mb))
    assert _range_chunks(_engine(engine, m_pad, tol), hops, windows) == want


@pytest.mark.parametrize("hops,windows,dim,budget_mb,want", [
    # the benchmark's cell: six walked columns of 602 features
    (2, 3, 602, 256, (1, "one_dispatch")),
    # the columns are walked over one block set: no width of a Range
    # makes a block grow, so none chunks
    (4, 3, 602, 256, (1, "one_dispatch")),
    (16, 3, 602, 256, (1, "one_dispatch")),
    (341, 3, 602, 256, (1, "one_dispatch")),
    # the tile budget sizes ``[m_pad, C]`` payloads, which this engine
    # has none of: its gathered step (65,536 rows x 640 lanes x 4 B =
    # 168 MB) is a constant of the table's size, so no budget sends a
    # Range to the ladder, where every chunk would gather that step too
    (4, 3, 602, 128, (1, "one_dispatch")),
    (2, 3, 602, 1, (1, "one_dispatch")),
    # nor does the width
    (4, 3, 128, 64, (1, "one_dispatch")),
    (4, 3, 129, 63, (1, "one_dispatch")),
])
def test_the_chunk_rule_on_an_f_wide_engine(monkeypatch, hops, windows, dim,
                                            budget_mb, want):
    """ISSUE 44: an engine whose column is an ``[n_pad, F]`` block
    answers ``dispatch_columns_ok`` from what grows with its columns —
    here nothing, since the columns are walked."""
    monkeypatch.setenv("RTPU_TILE_BUDGET_MB", str(budget_mb))
    hb = object.__new__(HopBatchedSGC)
    hb.tables = SimpleNamespace(m_pad=M_RANGE, n_pad=131_072)
    hb.dim = dim
    assert hb.warm_start_saves_steps is False
    assert _range_chunks(hb, hops, windows) == want
    # what the memory guard of the route counts: the masks and four
    # lane-padded blocks, whatever the columns
    lanes = -(-dim // 128) * 128
    assert hb.device_mask_bytes(6) == (M_RANGE + 131_072) * 6 \
        + 4 * 131_072 * lanes * 4


HOPS = [500, 600, 700, 800]
WINDOWS = (1000, 300, 100)


def _served(monkeypatch, log, program):
    """One served Range of 4 hops x 3 windows: its spans, its ledger and
    the rank columns the dispatch handed to the emit."""
    got = {}
    emit = Job._emit_columnar

    def grab(self, hops, windows, ranks, *rest):
        got["ranks"] = np.array(ranks)
        return emit(self, hops, windows, ranks, *rest)

    monkeypatch.setattr(Job, "_emit_columnar", grab)
    job = AnalysisManager(TemporalGraph(log)).submit(program, RangeQuery(
        start=HOPS[0], end=HOPS[-1], jump=100, windows=WINDOWS))
    return _spans(job), job.ledger.as_dict(), got["ranks"]


def test_a_fixed_superstep_range_is_one_dispatch_of_all_its_columns(
        traced, monkeypatch):
    log = _log(43)
    spans, led, ranks = _served(monkeypatch, log, _pagerank())
    (sweep,) = _named(spans, "sweep.columnar")
    assert sweep["args"]["hops"] == 4
    assert sweep["args"]["chunks"] == 1
    assert sweep["args"]["columns"] == 12
    assert sweep["args"]["chunk_rule"] == "one_dispatch"
    (compute,) = _named(spans, "hop.compute")
    assert compute["args"]["cols"] == 12
    assert compute["args"]["combine"] == "scan"
    assert {k: led["device"][k] for k in
            ("chunks", "columns", "chunk_rule")} == {
        "chunks": 1, "columns": 12, "chunk_rule": "one_dispatch"}
    assert led["device"]["dispatches"] == 1

    def engine():
        return HopBatchedPageRank(log, tol=0, max_steps=20)

    one, steps = engine().run(HOPS, WINDOWS, chunks=1)
    assert int(steps) == 20
    assert np.array_equal(ranks, np.asarray(one))          # bitwise
    two, _ = engine().run(HOPS, WINDOWS, chunks=2, warm_start=False)
    np.testing.assert_allclose(ranks, np.asarray(two), rtol=1e-5,
                               atol=1e-8)


def test_a_halting_pagerank_keeps_its_warm_started_chunks(traced,
                                                          monkeypatch):
    from raphtory_tpu.jobs import registry

    spans, led, _ = _served(
        monkeypatch, _log(44),
        registry.resolve("PageRank", {"max_steps": 20, "tol": 1e-7}))
    (sweep,) = _named(spans, "sweep.columnar")
    assert sweep["args"]["chunks"] == 2
    assert sweep["args"]["columns"] == 6
    assert sweep["args"]["chunk_rule"] == "warm_start"
    assert len(_named(spans, "hop.compute")) == 2
    assert led["device"]["chunk_rule"] == "warm_start"
