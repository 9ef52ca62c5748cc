"""A served mesh Range of CDLP (LDBC Graphalytics' community detection)
on the vertex-sharded route, through REST and the job layer, on four
virtual devices: it takes ``_try_range_mesh`` (``ShardedSweep`` +
``sharded.run``, a collective every round) on either dense route; its
rows equal the one-chip columnar route's and the plain reference's at
every (hop, window); the log's static partition is built once and held;
and ``/statusz`` reports the partition's padded rows and skew."""

import jax
import numpy as np
import pytest

from benchmark import client, gen, reference
from benchmark.algorithms import cdlp as ref_cdlp
from raphtory_tpu.core import events as ev
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.jobs.manager import AnalysisManager
from raphtory_tpu.jobs.rest import RestServer
from raphtory_tpu.obs.trace import TRACER
from raphtory_tpu.parallel import sharded

ALG = {"iterations": 10}
LIMITS = dict.fromkeys(ref_cdlp.COMPARED, 0)
N_IDS, T_SPAN, N_EVENTS = 300, 400, 4000
WINDOWS = [400, 120, 40]
SHARDS = 4


def _columns(seed):
    """Plain event columns ``(t, kind, s, d)`` in gen.py's codes: edge
    adds and deletes, vertex deletes and re-adds (revivals), self-loops
    and pairs joined both ways, over ids drawn out of a wider space."""
    rng = np.random.default_rng([seed, 0x4D455348])
    ids = np.sort(rng.choice(10 * N_IDS, N_IDS, replace=False))
    k = rng.choice(4, N_EVENTS, p=[0.08, 0.05, 0.72, 0.15]).astype(np.uint8)
    t = np.sort(rng.integers(0, T_SPAN, N_EVENTS)).astype(np.int64)
    # a skewed choice of endpoints: a few hubs, as a follow graph has
    s = ids[(N_IDS * rng.random(N_EVENTS) ** 2).astype(np.int64)]
    d = ids[(N_IDS * rng.random(N_EVENTS) ** 2).astype(np.int64)]
    edge = k >= gen.EADD
    loops = edge & (rng.random(N_EVENTS) < 0.03)
    d[loops] = s[loops]
    back = np.flatnonzero(edge)[1::9]                # (b, a) after (a, b)
    s[back], d[back] = d[back - 1], s[back - 1]
    k[back] = gen.EADD
    d[(k == gen.VADD) | (k == gen.VDEL)] = -1
    return t, k, s.astype(np.int64), d.astype(np.int64)


def _log(cols):
    t, k, s, d = cols
    code = np.array([ev.VERTEX_ADD, ev.VERTEX_DELETE, ev.EDGE_ADD,
                     ev.EDGE_DELETE], np.uint8)
    log = EventLog()
    log.append_batch(t, code[k], s, d)
    return log


def _body(start, end):
    return {"analyserName": "CDLP", "params": {"max_steps": 10},
            "windowType": "batched", "windowSet": WINDOWS, "start": start,
            "end": end, "jump": 20, "explain": 1}


class _Served:
    """A REST server over one log, traced; ``ask`` posts a Range and
    returns the finished job's document and its spans."""

    def __init__(self, log, mesh=None):
        self.mgr = AnalysisManager(TemporalGraph(log), mesh=mesh)
        self.srv = RestServer(self.mgr, port=0).start()
        self.rest = client.Rest(self.srv.port)

    def ask(self, start, end):
        job_id = self.rest.post("/RangeAnalysisRequest",
                                _body(start, end))["jobID"]
        job = self.mgr.get(job_id)
        assert job.wait(300) and job.status == "done", job.error
        doc = self.rest.results(job_id)
        return doc, self.rest.spans(doc["traceID"])

    def stop(self):
        self.srv.stop()


@pytest.fixture()
def traced():
    was = TRACER.enabled
    TRACER.enable()
    yield
    (TRACER.enable if was else TRACER.disable)()


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= SHARDS
    return sharded.make_mesh(SHARDS, 1, devices=jax.devices()[:SHARDS])


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def _fields(row):
    return {k: row[k] for k in ("time", "windowsize", "steps", "result")}


def _expected_partition(cols, n_pad):
    """Per-shard pair counts of both directions and the padded rows, from
    the event columns alone: every id and every (src, dst) pair the log
    mentions, the ids' ranks range-partitioned over the shards."""
    t, k, s, d = cols
    edge = k >= gen.EADD
    ids = np.unique(np.concatenate([s, d[edge]]))
    pairs = np.unique(np.stack([np.searchsorted(ids, s[edge]),
                                np.searchsorted(ids, d[edge])]), axis=1)
    n_loc = n_pad // SHARDS
    by_dst = np.bincount(pairs[1] // n_loc, minlength=SHARDS)
    by_src = np.bincount(pairs[0] // n_loc, minlength=SHARDS)
    pad = SHARDS * (sharded._pow2(int(by_dst.max()))
                    + sharded._pow2(int(by_src.max())))
    return by_dst.tolist(), by_src.tolist(), 2 * pairs.shape[1], pad


@pytest.mark.parametrize("comm", ["halo", "all_gather"])
def test_served_mesh_range_of_cdlp(comm, mesh, traced, monkeypatch):
    monkeypatch.setenv("RTPU_COMM_ROUTE", comm)
    cols = _columns(7)
    ref = reference.RefEvents(cols[0], cols[1], cols[2],
                              np.maximum(cols[3], 0), 10 * N_IDS)
    on_mesh, one_chip = _Served(_log(cols), mesh), _Served(_log(cols))
    try:
        asked = [(240, 280), (300, 340)]    # the second starts past the first
        got = [on_mesh.ask(*a) for a in asked]
        want = [one_chip.ask(*a) for a in asked]
        status = on_mesh.rest.get("/statusz")
    finally:
        on_mesh.stop()
        one_chip.stop()

    # (a) the route: the vertex-sharded sweep, a collective every round
    for doc, spans in got:
        routes = {s["args"]["route"] for s in _named(spans, "comm.exchange")}
        assert routes == {comm}
        assert len(_named(spans, "comm.exchange")) == 3     # one a hop
        # the rows of CDLP's segments come off the plans, once a dispatch
        assert {s["args"].get("mode_counts")
                for s in _named(spans, "comm.exchange")} == {"plan"}
        waits = _named(spans, "comm.block_wait")   # the one span of a wait
        assert [w["args"]["steps"] for w in waits] == [10, 10, 10]
        assert not _named(spans, "superstep.block")
        assert len(_named(spans, "comm.put")) == 3
        (build,) = _named(spans, "engine.build")
        assert build["args"]["engine"] == "ShardedSweep"
        led = doc["ledger"]
        assert not any(k.startswith("hopbatch.")
                       for k in led["device"]["kernels"])
        assert set(led["dcn"]["routes"]) == {comm} and led["dcn"]["bytes"] > 0
        assert not _named(spans, "hop.compute")
    for doc, spans in want:
        assert list(doc["ledger"]["device"]["kernels"]) \
            == ["hopbatch.delta.cdlp"]
        assert not _named(spans, "comm.exchange")

    # (b) the rows: the one-chip route's, field for field, and the
    # reference's at every (hop, window)
    for (doc, _), (doc1, _) in zip(got, want):
        rows, rows1 = doc["results"], doc1["results"]
        assert len(rows) == 3 * len(WINDOWS)
        assert [_fields(r) for r in rows] == [_fields(r) for r in rows1]
        for row in rows:
            assert row["steps"] == 10
            cmp_ = ref_cdlp.compare(row, ref_cdlp.reference(
                *ref.fold(row["time"], row["windowsize"]), ALG), LIMITS, ALG)
            assert cmp_["ok"], (row["time"], row["windowsize"], cmp_)
    assert len({r["result"]["label_checksum"]
                for doc, _ in got for r in doc["results"]}) > 3

    # (c) the log's partition is built once; the second request's fold
    # starts from the first's checkpoint, not from the log's first event
    (first,), (second,) = (_named(spans, "partition.build")
                           for _, spans in got)
    assert first["args"]["status"] == "built"
    assert second["args"]["status"] == "held"
    layout = {k: first["args"][k] for k in ("shards", "rows", "pad_rows",
                                            "halo_rows", "pad_factor")}
    assert {k: second["args"][k] for k in layout} == layout
    seeds = [[s["args"]["seed"] for s in _named(spans, "fold.seed")
              if "seed" in s["args"]] for _, spans in got]
    assert seeds[1] == ["checkpoint"]
    for _, spans in got:
        patches = _named(spans, "partition.patch")
        assert len(patches) == 3 and patches[0]["args"].get("seed")
        assert patches[0]["args"]["rows"] >= layout["rows"]
    # what a hop ships: the masks and the vertex times, never the blocks
    # the partition holds on the devices
    puts = [s["args"] for _, spans in got for s in _named(spans, "comm.put")]
    assert puts[0]["resident_bytes"] > 0
    assert all(p["resident_bytes"] == 0 for p in puts[1:])
    assert len({p["bytes"] for p in puts}) == 1

    # (d) /statusz: the partition's padded rows and the skew by shard
    n_pad = _named(got[0][1], "engine.build")[0]["args"]["n_pad"]
    by_dst, by_src, rows, pad_rows = _expected_partition(cols, n_pad)
    coll = status["collectives"]
    assert coll["partition"] == layout
    assert (layout["shards"], layout["rows"], layout["pad_rows"]) \
        == (SHARDS, rows, pad_rows)
    assert layout["pad_factor"] == round(pad_rows / rows, 4)
    # the skew is the partition's (every pair row the log holds) until a
    # quarter of the rows has been patched, then the live rows' at that hop
    n_loc = n_pad // SHARDS
    ids = np.unique(np.concatenate([cols[2], cols[3][cols[1] >= gen.EADD]]))

    def live(T, end):
        _, src, dst = ref.fold(T, None)
        at = np.searchsorted(ids, dst if end == "dst" else src)
        return np.bincount(at // n_loc, minlength=SHARDS).tolist()

    hops = [T for a, b in asked for T in range(a, b + 1, 20)]
    for kind, static, end in (("edges_dst", by_dst, "dst"),
                              ("edges_src", by_src, "src")):
        seen = coll["skew"][kind]
        assert seen["per_shard"] in [static] + [live(T, end) for T in hops]
        assert seen["max"] == max(seen["per_shard"])
        assert seen["skew"] == round(
            seen["max"] / (sum(seen["per_shard"]) / SHARDS), 4)
    assert coll["skew_builds"] >= 1
    # the ledger's mode rows: the padded rows, a window, a round, a hop
    for doc, _ in got:
        assert doc["ledger"]["device"]["mode_rows"] \
            == pad_rows * len(WINDOWS) * 10 * 3
