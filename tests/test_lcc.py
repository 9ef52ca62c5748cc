"""LCC (LDBC Graphalytics' local clustering coefficient): the plain numpy
reference of the benchmark (``benchmark/algorithms/lcc.py``) against the
definition's own walk of Python sets, and the program — the triangle
table, the columnar kernel, a served Range and View on the
``hopbatch.delta.lcc`` route — against that reference."""

import numpy as np
import pytest

from benchmark import gen, reference
from benchmark.algorithms import lcc as ref_lcc
from raphtory_tpu.core import events as ev
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import (AnalysisManager, LiveQuery,
                                       RangeQuery, ViewQuery)
from raphtory_tpu.ops import triangles

ALG = {"iterations": 1}
LIMITS = {**dict.fromkeys(ref_lcc.COMPARED, 0),
          "lcc_mean_rel_err": 1e-6, "lcc_max_rel_err": 1e-6}
N_IDS, T_SPAN = 40, 100


def _columns(seed, n_events=900):
    """Plain event columns ``(t, kind, s, d)`` in gen.py's codes: edge
    adds and deletes, vertex deletes and re-adds (revivals), self-loops,
    pairs joined both ways, and vertex 39, added once and never joined."""
    rng = np.random.default_rng(seed)
    k = rng.choice(4, n_events, p=[0.06, 0.04, 0.75, 0.15]).astype(np.uint8)
    t = np.sort(rng.integers(0, T_SPAN, n_events)).astype(np.int64)
    s = rng.integers(0, N_IDS - 1, n_events).astype(np.int64)
    d = rng.integers(0, N_IDS - 1, n_events).astype(np.int64)
    edge = k >= gen.EADD
    loops = edge & (rng.random(n_events) < 0.05)
    d[loops] = s[loops]                              # self-loops
    back = np.flatnonzero(edge)[::5]                 # (b, a) after (a, b)
    s[back], d[back] = d[back - 1], s[back - 1]
    k[back] = gen.EADD
    k[0], t[0], s[0] = gen.VADD, 0, N_IDS - 1        # the isolated vertex
    d[(k == gen.VADD) | (k == gen.VDEL)] = -1
    return t, k, s, d


def _log(cols):
    t, k, s, d = cols
    code = np.array([ev.VERTEX_ADD, ev.VERTEX_DELETE, ev.EDGE_ADD,
                     ev.EDGE_DELETE], np.uint8)
    log = EventLog()
    log.append_batch(t, code[k], s, d)
    return log


def _ref(cols):
    t, k, s, d = cols
    return reference.RefEvents(t, k, s, np.maximum(d, 0), N_IDS)


def _walk(vm, src, dst):
    """The definition, one vertex at a time, in Python sets."""
    edges = {(a, b) for a, b in zip(src.tolist(), dst.tolist()) if a != b}
    near = {int(v): set() for v in np.flatnonzero(vm)}
    for a, b in edges:
        near[a].add(b)
        near[b].add(a)
    deg = {v: len(ns) for v, ns in near.items()}
    tri = {v: sum((u, w) in edges for u in ns for w in ns)
           for v, ns in near.items()}
    return deg, tri


VIEWS = [(95, None), (95, 30), (60, 12)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_the_definitions_walk_of_sets(seed):
    r = _ref(_columns(seed))
    saw_loop = saw_both = saw_alone = saw_tri = False
    for T, w in VIEWS:
        vm, src, dst = r.fold(T, w)
        deg, tri = ref_lcc.counts(vm, src, dst)
        want_deg, want_tri = _walk(vm, src, dst)
        assert {v: int(deg[v]) for v in want_deg} == want_deg
        assert {v: int(tri[v]) for v in want_tri} == want_tri
        row = ref_lcc.reference(vm, src, dst, ALG)
        lcc = [t / (deg[v] * (deg[v] - 1)) if deg[v] > 1 else 0.0
               for v, t in want_tri.items()]
        assert row["lcc_mean"] == pytest.approx(np.mean(lcc), rel=1e-12)
        assert row["edges_among_neighbours"] == sum(want_tri.values())
        pairs = set(zip(src.tolist(), dst.tolist()))
        saw_loop |= any(a == b for a, b in pairs)
        saw_both |= any((b, a) in pairs for a, b in pairs if a != b)
        saw_alone |= any(d == 0 for d in want_deg.values())
        saw_tri |= sum(want_tri.values()) > 100
    assert saw_loop and saw_both and saw_alone and saw_tri


def _table_of(log):
    from raphtory_tpu.engine.device_sweep import LogIndex

    t = LogIndex(log.freeze()).tables
    return t, triangles.build_table(t.e_src, t.e_dst, t.m, t.n, t.n_pad,
                                    t.m_pad, tile_rows=1024, tile_edges=128)


@pytest.mark.parametrize("seed", [0, 3])
def test_triangle_table_lists_each_triangle_once_in_both_builders(seed):
    log = _log(_columns(seed, n_events=2500))
    t, tt = _table_of(log)
    slow = triangles.build_table(t.e_src, t.e_dst, t.m, t.n, t.n_pad,
                                 t.m_pad, tile_rows=1024, tile_edges=128,
                                 native=False)
    for name in tt.ARRAYS:
        np.testing.assert_array_equal(getattr(tt, name), getattr(slow, name))
    assert tt.rows.shape[1] > 1                   # more tiles than one
    # the all-pairs graph's triangles, from the definition
    s, d = t.e_src[:t.m].astype(int), t.e_dst[:t.m].astype(int)
    near = {}
    for a, b in zip(s, d):
        if a != b:
            near.setdefault(a, set()).add(b)
            near.setdefault(b, set()).add(a)
    want = sum(len(near[a] & near[b]) for a in near for b in near[a]) // 6
    assert tt.triangles == want > 50
    assert tt.edges == sum(map(len, near.values())) // 2
    real = tt.rows[:, tt.rows[0] != tt.edges]
    assert real.shape[1] == want and len(set(map(tuple, real.T))) == want
    # a tile credits only the edge ids from its first on
    for k, first in enumerate(tt.tile_lo):
        rows = tt.rows[:2, k][:, tt.rows[0, k] != tt.edges]
        assert rows.size == 0 or (rows.min() >= first
                                  and rows.max() < first + tt.tile_edges)


@pytest.mark.parametrize("columns", [3, 17])
def test_kernel_counts_equal_the_reference_under_any_masks(columns):
    import jax
    import jax.numpy as jnp

    log = _log(_columns(5, n_events=2500))
    t, tt = _table_of(log)
    rng = np.random.default_rng(columns)
    me = np.zeros((t.m_pad, columns), bool)
    me[:t.m] = rng.random((t.m, columns)) < np.linspace(1.0, 0.1, columns)
    run = jax.jit(triangles.lcc_columns, static_argnums=(1, 2))
    out = np.asarray(run(jnp.asarray(me), t.n_pad, tt.tile_edges,
                         *map(jnp.asarray, tt.device_args())))
    assert out.shape == (columns, 2, t.n_pad) and out.dtype == np.int32
    for c in range(columns):
        keep = me[:t.m, c]
        deg, tri = ref_lcc.counts(np.ones(t.n_pad, bool),
                                  t.e_src[:t.m][keep], t.e_dst[:t.m][keep])
        np.testing.assert_array_equal(out[c, 0], tri)
        np.testing.assert_array_equal(out[c, 1], deg)


def _serve(log, program, q, graph=None, ok=True):
    from raphtory_tpu.obs.trace import TRACER

    was = TRACER.enabled
    TRACER.enable()
    try:
        job = AnalysisManager(graph or TemporalGraph(log)).submit(program, q)
        assert job.wait(300)
        if ok:
            assert job.status == "done", job.error
        spans = [e for e in TRACER.for_trace(job.trace_id) if e["ph"] == "X"]
    finally:
        (TRACER.enable if was else TRACER.disable)()
    return job, spans


def _check_rows(job, r, n_rows):
    rows = job.results_snapshot()
    assert len(rows) == n_rows
    for row in rows:
        want = ref_lcc.reference(*r.fold(row["time"], row["windowsize"]),
                                 ALG)
        cmp_ = ref_lcc.compare(row, want, LIMITS, ALG)
        assert cmp_["ok"], (row["time"], row["windowsize"], cmp_)
    return rows


@pytest.mark.parametrize("seed,windows", [(0, (100, 30, 12)), (1, (30, 12)),
                                          (2, None)])
def test_served_range_rides_the_columnar_route_and_equals_the_reference(
        seed, windows):
    cols = _columns(seed)
    log, r = _log(cols), _ref(cols)
    q = RangeQuery(start=60, end=90, jump=30, windows=windows)
    job, spans = _serve(log, registry.resolve("LCC", {}), q)
    names = {s["name"] for s in spans}
    assert "hop.compute" in names and "sweep.columnar" in names
    assert not names & {"bsp.dispatch", "snapshot.fold"}
    (compute,) = [s for s in spans if s["name"] == "hop.compute"]
    assert compute["args"]["kind"] == "lcc"       # one dispatch a request
    assert compute["args"]["combine"] == "intersect"
    (build,) = [s for s in spans if s["name"] == "engine.build"]
    assert build["args"]["engine"] == "HopBatchedLCC"
    (tri,) = [s for s in spans if s["name"] == "index.triangles"]
    assert tri["args"]["triangles"] > 0 and tri["args"]["nbytes"] > 0
    assert 0 < tri["args"]["pairs"] <= build["args"]["m_pad"]
    assert build["ts"] <= tri["ts"] \
        and tri["ts"] + tri["dur"] <= build["ts"] + build["dur"]
    led = job.ledger.as_dict()
    assert [k for k in led["device"]["kernels"]] == ["hopbatch.delta.lcc"]
    n_w = len(windows or (None,))
    assert led["device"]["triangle_rows"] == tri["args"]["rows"] * 2 * n_w
    rows = _check_rows(job, r, 2 * n_w)
    assert all(row["steps"] == 1 for row in rows)
    assert any(row["result"]["edges_among_neighbours"] > 0 for row in rows)


def test_a_view_is_the_same_engine_at_one_hop_and_live_names_the_route():
    cols = _columns(4)
    log, r = _log(cols), _ref(cols)
    job, spans = _serve(log, registry.resolve("LCC", {}),
                        ViewQuery(80, windows=(100, 20)))
    (compute,) = [s for s in spans if s["name"] == "hop.compute"]
    assert compute["args"]["kind"] == "lcc" and compute["args"]["cols"] == 2
    assert "bsp.dispatch" not in {s["name"] for s in spans}
    _check_rows(job, r, 2)
    job, spans = _serve(log, registry.resolve("LCC", {}),
                        LiveQuery(repeat=0.01, max_runs=1), ok=False)
    assert job.status == "failed" and "hopbatch.delta.lcc" in job.error
    assert "bsp.dispatch" not in {s["name"] for s in spans}


def test_the_table_is_built_for_lcc_alone_and_once_a_log():
    from raphtory_tpu.engine.device_sweep import log_index_status

    cols = _columns(6)
    log, r = _log(cols), _ref(cols)
    graph = TemporalGraph(log)
    q = RangeQuery(start=60, end=90, jump=30, windows=(30,))
    before = log_index_status()["bytes"]
    _, spans = _serve(log, registry.resolve("PageRank", {}), q, graph)
    assert "index.triangles" not in {s["name"] for s in spans}
    plain = log_index_status()["bytes"]
    assert plain > before
    built = []
    for _ in range(2):
        job, spans = _serve(log, registry.resolve("LCC", {}), q, graph)
        built.append(sum(s["name"] == "index.triangles" for s in spans))
        (build,) = [s for s in spans if s["name"] == "engine.build"]
        assert build["args"]["index"] == "hit"
        _check_rows(job, r, 2)
    assert built == [1, 0]
    # /statusz log_index.bytes counts the table; PageRank's did not
    (tri,) = [s for s in _serve(_log(cols), registry.resolve("LCC", {}),
                                q)[1] if s["name"] == "index.triangles"]
    assert log_index_status()["bytes"] - plain >= tri["args"]["nbytes"]


def test_each_nearest_wrong_count_fails_the_comparison():
    failed = 0
    for seed in (0, 1, 2):
        r = _ref(_columns(seed))
        for T, w in VIEWS:
            vm, src, dst = r.fold(T, w)
            want = ref_lcc.reference(vm, src, dst, ALG)
            assert ref_lcc.compare(ref_lcc.stated(vm, src, dst, ALG), want,
                                   LIMITS, ALG)["ok"]
            failed += not ref_lcc.compare(
                ref_lcc.control(vm, src, dst, ALG), want, LIMITS, ALG)["ok"]
    assert failed == 9      # mutual pairs in every view: counted once


@pytest.mark.parametrize("rows,segments", [(1, 1), (300, 7), (5000, 900)])
def test_integer_sums_spread_and_lookup_equal_numpy(rows, segments):
    """The three pieces a tile is made of, against numpy: a running sum
    differenced at the segments' ends (exact where the prefix wraps), a
    value spread along sorted segments (empty ones among them), a lane
    row lookup."""
    import jax.numpy as jnp

    from raphtory_tpu.ops.segment import integer_segment_sums, rows_upto

    rng = np.random.default_rng(rows)
    ids = np.sort(rng.integers(0, max(segments - 2, 1), rows)).astype(
        np.int32)                                  # the last ones empty
    x = rng.integers(0, 2**31 - 1, (3, rows)).astype(np.int32)
    x[:, rows // 2:] //= max(rows, 1)              # the prefix wraps early
    upto = rows_upto(jnp.asarray(ids), segments)
    np.testing.assert_array_equal(
        np.asarray(upto), np.searchsorted(ids, np.arange(segments), "right"))
    want = np.zeros((3, segments), np.int64)
    for c in range(3):
        np.add.at(want[c], ids, x[c].astype(np.int64))
    got = np.asarray(integer_segment_sums(jnp.asarray(x), upto))
    np.testing.assert_array_equal(got, want.astype(np.int32))  # mod 2^32
    vals = rng.integers(0, 2**32, segments, dtype=np.uint32)
    spread = np.asarray(triangles._spread(jnp.asarray(vals), upto, rows))
    np.testing.assert_array_equal(spread, vals[ids])
    table = rng.integers(0, 2**32, (9, 128), dtype=np.uint32)
    e = rng.integers(0, 9 * 128, rows).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(triangles._lookup(jnp.asarray(table), jnp.asarray(e))),
        table.reshape(-1)[e])
