"""Example-domain parity: parsers, sources, and domain analysers (§2.8)."""

import json

import numpy as np

from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp
from raphtory_tpu.examples import (
    BitcoinBlockParser,
    ChainalysisABParser,
    CitationParser,
    EthereumTaintTracking,
    EthereumTransactionParser,
    GabMostUsedTopics,
    GabUserGraphParser,
    LDBCParser,
    RandomCommandSource,
    RandomJsonParser,
    RumourParser,
    TrackAndTraceParser,
    location_id,
)
from raphtory_tpu.ingestion.pipeline import IngestionPipeline
from raphtory_tpu.ingestion.source import IterableSource
from raphtory_tpu.ingestion.updates import (
    EdgeAdd,
    EdgeDelete,
    VertexAdd,
    VertexDelete,
    assign_id,
)


def _ingest(records, parser):
    pipe = IngestionPipeline()
    pipe.add_source(IterableSource(records, name="t"), parser)
    pipe.run()
    assert not pipe.errors, pipe.errors
    return pipe.log


# ---- random (wire-format JSON commands) ----

def test_random_command_roundtrip():
    src = RandomCommandSource(2_000, id_pool=300, seed=7,
                              mix=(0.3, 0.4, 0.1, 0.2))
    par = RandomJsonParser()
    kinds = {"VertexAdd": 0, "EdgeAdd": 0, "VertexRemoval": 0,
             "EdgeRemoval": 0}
    log = _ingest(list(src), par)
    for cmd in RandomCommandSource(2_000, id_pool=300, seed=7,
                                   mix=(0.3, 0.4, 0.1, 0.2)):
        kinds[next(iter(json.loads(cmd)))] += 1
    assert log.n >= 2_000  # vertex adds carry props; every command lands
    assert kinds["EdgeAdd"] > kinds["VertexAdd"] > kinds["EdgeRemoval"] > 0
    # graph is queryable
    g = TemporalGraph(log)
    v = g.view_at(g.latest_time)
    assert v.n_active > 0


def test_random_json_parser_fields():
    par = RandomJsonParser()
    (u,) = par('{"VertexAdd":{"messageID": 5, "srcID": 9, '
               '"properties": {"prop1": 0.5}}}')
    assert u == VertexAdd(5, 9, {"prop1": 0.5})
    (u,) = par('{"EdgeRemoval":{"messageID": 6, "srcID": 1, "dstID": 2}}')
    assert u == EdgeDelete(6, 1, 2)
    assert par('{"Bogus": {}}') == []


# ---- gab ----

def test_gab_user_graph_parser():
    par = GabUserGraphParser()
    rows = par("2016-08-10 13:58:06;post1;101;x;post0;202")
    assert [type(r) for r in rows] == [VertexAdd, VertexAdd, EdgeAdd]
    t = rows[2].time
    assert rows[2] == EdgeAdd(t, 101, 202)
    assert t == 1470837486
    # non-positive parent → dropped, like the reference's targetNode > 0
    assert par("2016-08-10 13:58:06;p;101;x;p;-1") == []


def test_gab_most_used_topics():
    log = _ingest(
        [  # two topics, one user posting to them
            VertexAdd(1, 1, {"!type": "topic", "!id": "t/news",
                             "!title": "News"}),
            VertexAdd(1, 2, {"!type": "topic", "!id": "t/cats",
                             "!title": "Cats"}),
            VertexAdd(1, 10, {"!type": "user"}),
            VertexAdd(1, 11, {"!type": "user"}),
            EdgeAdd(2, 10, 1), EdgeAdd(3, 11, 1), EdgeAdd(4, 10, 2),
        ],
        None,
    )
    view = build_view(log, 10)
    prog = GabMostUsedTopics(top_k=5)
    res, _ = bsp.run(prog, view)
    out = prog.reduce(res, view)
    assert [t["id"] for t in out["topics"]] == ["t/news", "t/cats"]
    assert out["topics"][0] == {"id": "t/news", "title": "News", "uses": 2}


# ---- blockchain ----

def test_ethereum_transaction_parser_and_taint():
    rows = []
    # a pays b at t=100, b pays c at t=200, c paid d at t=50 (before taint)
    for frm, to, tx, t in [("a", "b", "t1", 100), ("b", "c", "t2", 200),
                           ("c", "d", "t0", 50)]:
        rows.append(f"{frm},{to},{tx},{t}")
    log = _ingest(rows, EthereumTransactionParser())
    g = TemporalGraph(log)
    view = g.view_at(g.latest_time, include_occurrences=True)
    prog = EthereumTaintTracking(seeds=(assign_id("a"),), start_time=0)
    res, _ = bsp.run(prog, view)
    out = prog.reduce(res, view)
    infected = {r["id"] for r in out["infections"]}
    # taint flows a→b→c forward in time but NOT c→d (t=50 predates taint of c)
    assert infected == {assign_id("a"), assign_id("b"), assign_id("c")}


def test_ethereum_burn_goes_to_null_wallet():
    (va, vb, e) = EthereumTransactionParser()("a,,tx9,7")
    assert vb.vid == assign_id("null")
    assert e.time == 7000


def test_bitcoin_block_parser():
    block = {
        "time": 1000, "height": 5, "hash": "hh",
        "tx": [
            {"txid": "tx1",
             "vin": [{"coinbase": "00"}],
             "vout": [{"value": 25.0, "n": 0,
                       "scriptPubKey": {"addresses": ["addrA"]}}]},
            {"txid": "tx2",
             "vin": [{"txid": "tx1", "vout": 0}],
             "vout": [{"value": 24.0, "n": 0,
                       "scriptPubKey": {"addresses": ["addrB"]}}]},
        ],
    }
    log = _ingest([block], BitcoinBlockParser())
    g = TemporalGraph(log)
    v = g.view_at(g.latest_time)
    # coingen → tx1 → addrA ; tx1 → tx2 → addrB
    li = v.local_index([BitcoinBlockParser.COINGEN, assign_id("tx1")])
    assert (li >= 0).all()
    assert v.out_deg[li[0]] == 1      # coingen feeds tx1
    assert v.out_deg[li[1]] == 2      # tx1 → addrA and → tx2
    types = v.vertex_prop_str("type")
    assert "transaction" in types and "address" in types


def test_chainalysis_parser():
    rows = ChainalysisABParser()("tx1,10,20,1.5,60000.0,777")
    assert len(rows) == 5
    log = _ingest(["tx1,10,20,1.5,60000.0,777"], ChainalysisABParser())
    v = build_view(log, 1000)
    btc = v.edge_prop("BitCoin")
    assert np.nanmax(btc) == 1.5


# ---- ldbc ----

def test_ldbc_parser_with_deletions():
    row = ("person_knows_person|2012-11-01T09:28:01.185+00:00|"
           "2019-07-22T11:24:24.362+00:00|35184372093644|123")
    par = LDBCParser(edge_deletion=True)
    add, dele = par(row)
    assert isinstance(add, EdgeAdd) and isinstance(dele, EdgeDelete)
    assert add.src == assign_id("person35184372093644")
    assert dele.time > add.time
    prow = ("person|2012-11-01T09:28:01.185+00:00|"
            "2019-07-22T11:24:24.362+00:00|35184372093644|Jose|Garcia")
    (vadd,) = LDBCParser()(prow)
    assert isinstance(vadd, VertexAdd)
    (v1, v2) = LDBCParser(vertex_deletion=True)(prow)
    assert isinstance(v2, VertexDelete)


# ---- citations ----

def test_citation_parser_last_cite_tombstone():
    par = CitationParser()
    rows = par("1, 2, 10/01/2020, 05/01/2020, 10/01/2020")
    assert [type(r) for r in rows] == [VertexAdd, VertexAdd, EdgeAdd,
                                       EdgeDelete]
    rows = par("1, 2, 10/01/2020, 05/01/2020, 11/01/2020")
    assert [type(r) for r in rows] == [VertexAdd, VertexAdd, EdgeAdd]
    assert rows[1].time < rows[0].time  # target existed before the citation


# ---- track and trace ----

def test_track_and_trace_grid():
    # same cell → same location id; far away → different
    assert location_id(0.5, 0.5) == location_id(0.5, 0.5)
    assert location_id(0.5, 0.5) != location_id(0.6, 0.6)
    par = TrackAndTraceParser(user_col=0, lat_col=1, lon_col=2, time_col=3)
    rows = par("42, 0.5, 0.5, 1600000000")
    assert [type(r) for r in rows] == [VertexAdd, VertexAdd, EdgeAdd]
    assert rows[2].src == 42 and rows[2].dst == location_id(0.5, 0.5)
    assert rows[0].time == 1600000000000


# ---- twitter rumour ----

def test_rumour_parser():
    tweet = {"created_at": "Wed Aug 10 13:58:06 +0000 2016",
             "user": {"id": 7}, "in_reply_to_user_id": 9}
    (e,) = RumourParser()(("rumour", json.dumps(tweet)))
    assert e == EdgeAdd(1470837486000, 7, 9, {"!rumourStatus": "rumour"})
    tweet["in_reply_to_user_id"] = None
    (v,) = RumourParser()("nonrumour__" + json.dumps(tweet))
    assert isinstance(v, VertexAdd)
    assert v.props == {"!rumourStatus": "nonrumour"}
    # immutable property survives later writes (first wins)
    log = _ingest([EdgeAdd(1, 1, 2, {"!s": "first"}),
                   EdgeAdd(5, 1, 2, {"!s": "second"})], None)
    v = build_view(log, 10)
    assert list(v.edge_prop_str("s"))[: v.m_active].count("first") == 1


def test_ldbc_empty_deletion_column_still_adds():
    # deletion column only parsed when a deletion flag is on (reference
    # default: LDBC_*_DELETION=false) — empty col must not drop the add
    row = "person|2012-11-01T09:28:01.185+00:00||35184372093644|Jose"
    (v,) = LDBCParser()(row)
    assert isinstance(v, VertexAdd)
    # with the flag on and an unparsable deletion date, the add still lands
    (v2,) = LDBCParser(vertex_deletion=True)(row)
    assert isinstance(v2, VertexAdd)


def test_malformed_records_never_kill_the_source():
    # one bad record kills a source thread if the parser raises — every
    # domain parser must drop, not raise
    bad = ["no-separator-here", "{not json", '{"weird": []}',
           '{"VertexAdd": {"messageID": "NaN"}}', ""]
    for parser in (RumourParser(), RandomJsonParser(), BitcoinBlockParser(),
                   EthereumTransactionParser(), LDBCParser(),
                   CitationParser(), TrackAndTraceParser(),
                   GabUserGraphParser(), ChainalysisABParser()):
        for rec in bad:
            assert parser(rec) == [], (parser, rec)
    assert RumourParser()(("tag", "{broken")) == []
    assert BitcoinBlockParser()({"time": "x"}) == []


def test_gab_raw_post_parser_unfolds_hetero_graph():
    """One raw JSON post → post/user/topic vertices, the four typed edges,
    and a single-level parent unfold (GabRawRouter.scala:28-130)."""
    from raphtory_tpu.examples.gab import GabRawPostParser
    from raphtory_tpu.ingestion.updates import assign_id

    parent = {"id": 7, "created_at": "2016-08-10T12:00:00+00:00",
              "user": {"id": 2, "name": "P", "username": "p",
                       "verified": False},
              "parent": {"id": 99, "created_at": "2016-08-10T11:00:00",
                         "user": None}}
    post = {"id": 5, "created_at": "2016-08-10 13:58:06", "score": 3,
            "like_count": 4,
            "user": {"id": 1, "name": "A", "username": "a",
                     "verified": True},
            "topic": {"id": "t1", "created_at": "2016-08-01",
                      "title": "News", "category": 2},
            "parent": parent}
    updates = GabRawPostParser()(json.dumps(post))

    vadds = [u for u in updates if isinstance(u, VertexAdd)]
    eadds = [u for u in updates if isinstance(u, EdgeAdd)]
    # post+user+topic for the child, post+user for the parent; the
    # grandparent (depth 2) is NOT unfolded — one recursion per post
    assert len(vadds) == 5
    types = sorted(u.props["!type"] for u in eadds)
    assert types == ["childToParent", "postToTopic", "postToUser",
                     "postToUser", "userToPost", "userToPost"]
    # child→parent at the CHILD's time (deliberate fix of the reference's
    # inverted, parent-stamped edge — see the parser docstring)
    c2p = next(u for u in eadds if u.props["!type"] == "childToParent")
    assert c2p.src == assign_id("gab:post:5")
    assert c2p.dst == assign_id("gab:post:7")
    assert c2p.time == 1470837486

    # drives the pipeline end-to-end and the topic analyser sees the topic
    pipe = IngestionPipeline()
    pipe.add_source(IterableSource([json.dumps(post)], name="raw"),
                    GabRawPostParser())
    pipe.run()
    assert not pipe.errors and pipe.counts["raw"] == len(updates)
    g = TemporalGraph(pipe.log, pipe.watermarks)
    v = g.view_at(1470837486)
    assert v.n_active == 5
    tprop = v.vertex_prop_str("type")
    assert sorted(x for x in tprop if x) .count("post") == 2
    assert "topic" in tprop and "user" in tprop

    # malformed lines drop, not raise
    assert GabRawPostParser()("not json") == []
    assert GabRawPostParser()('{"id": null}') == []
    ok = '"id": 1, "created_at": "2016-08-10 13:58:06"'
    # truthy non-dict sub-objects are ignored, not fatal
    assert len(GabRawPostParser()('{%s, "topic": "news"}' % ok)) == 1
    assert len(GabRawPostParser()('{%s, "user": "bob"}' % ok)) == 1
    assert len(GabRawPostParser()('{%s, "parent": [1]}' % ok)) == 1
