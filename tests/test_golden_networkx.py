"""Golden-value algorithm tests against NetworkX (SURVEY §4: the test
pyramid the reference lacks needs external oracles, not just
engine-vs-engine equivalence — all our engines could share one bug), and
on windowed views with deletes against the benchmark's plain fold."""

import networkx as nx
import numpy as np
import pytest

from benchmark import reference
from benchmark.algorithms import pagerank as ref_pagerank
from raphtory_tpu.algorithms import (BFS, SSSP, ConnectedComponents,
                                     DegreeBasic, PageRank)
from raphtory_tpu.core import events as ev
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp
from raphtory_tpu.utils.synth import ldbc_like_log, random_update_stream


def to_networkx(view, weight_prop=None):
    """Oracle-side mirror of a GraphView's alive vertex/edge sets (absent
    weights default to 1.0, matching SSSP.message)."""
    w_arr = view.edge_prop(weight_prop) if weight_prop else None
    G = nx.DiGraph()
    for i in range(view.n_active):
        G.add_node(int(view.vids[i]))
    for p in range(view.m_active):
        attrs = {}
        if w_arr is not None:
            w = float(w_arr[p])
            attrs["weight"] = 1.0 if np.isnan(w) else w
        G.add_edge(int(view.vids[view.e_src[p]]),
                   int(view.vids[view.e_dst[p]]), **attrs)
    return G


@pytest.fixture(scope="module")
def graph():
    log = EventLog()
    log.append_batch(*random_update_stream(
        3000, id_pool=150, seed=13, t_end=1000,
        mix=(0.25, 0.55, 0.08, 0.12)))
    view = build_view(log, 900)
    return view, to_networkx(view)


def test_pagerank_matches_networkx(graph):
    view, G = graph
    got, _ = bsp.run(PageRank(max_steps=200, tol=1e-12), view)
    got = np.asarray(got)
    want = nx.pagerank(G, alpha=0.85, max_iter=500, tol=1e-12)
    for i in range(view.n_active):
        assert got[i] == pytest.approx(want[int(view.vids[i])], abs=2e-6), \
            int(view.vids[i])


def test_connected_components_match_networkx(graph):
    view, G = graph
    got, _ = bsp.run(ConnectedComponents(max_steps=200), view)
    got = np.asarray(got)
    ours = {}
    for i in range(view.n_active):
        ours.setdefault(int(got[i]), set()).add(int(view.vids[i]))
    theirs = list(nx.connected_components(G.to_undirected()))
    assert sorted(map(sorted, ours.values())) == \
        sorted(map(sorted, theirs))


def test_bfs_matches_networkx(graph):
    view, G = graph
    seeds = tuple(int(v) for v in view.vids[:3])
    dist, _ = bsp.run(BFS(seeds=seeds, directed=False, max_steps=200), view)
    dist = np.asarray(dist)
    U = G.to_undirected()
    want = {}
    for s in seeds:
        for v, d in nx.single_source_shortest_path_length(U, s).items():
            want[v] = min(want.get(v, np.inf), d)
    for i in range(view.n_active):
        vid = int(view.vids[i])
        w = want.get(vid, np.inf)
        g = float(dist[i])
        assert (np.isinf(w) and np.isinf(g)) or g == w, (vid, g, w)


def test_weighted_sssp_matches_networkx_dijkstra():
    log = ldbc_like_log(n_persons=120, n_knows=900, t_span=1000,
                        weighted=True, seed=7)
    view = build_view(log, 1000)
    G = to_networkx(view, weight_prop="weight")
    seeds = tuple(int(v) for v in view.vids[:2])
    dist, _ = bsp.run(SSSP(seeds=seeds, weight_prop="weight", directed=True,
                           max_steps=300), view)
    dist = np.asarray(dist)
    want = nx.multi_source_dijkstra_path_length(G, set(seeds),
                                                weight="weight")
    for i in range(view.n_active):
        vid = int(view.vids[i])
        w = want.get(vid, np.inf)
        g = float(dist[i])
        assert (np.isinf(w) and np.isinf(g)) or \
            g == pytest.approx(w, abs=1e-4), (vid, g, w)


def test_degrees_match_networkx(graph):
    view, G = graph
    got, _ = bsp.run(DegreeBasic(), view)
    for i in range(view.n_active):
        vid = int(view.vids[i])
        assert int(np.asarray(got["in"])[i]) == G.in_degree(vid)
        assert int(np.asarray(got["out"])[i]) == G.out_degree(vid)


def _adversarial_stream(seed, n_events=600, n_ids=14, t_span=60):
    """Time-sorted stream with heavy id reuse, duplicate timestamps,
    vertex/edge deletes and re-adds (revivals): the engine's log and the
    benchmark's plain reference of the same events."""
    rng = np.random.default_rng(seed)
    code = np.array([ev.VERTEX_ADD, ev.VERTEX_DELETE, ev.EDGE_ADD,
                     ev.EDGE_DELETE], np.uint8)     # RefEvents' 0..3
    k = rng.choice(4, n_events, p=[0.2, 0.1, 0.5, 0.2])
    t = np.sort(rng.integers(0, t_span, n_events)).astype(np.int64)
    s = rng.integers(0, n_ids, n_events).astype(np.int64)
    d = rng.integers(0, n_ids, n_events).astype(np.int64)
    d[k < 2] = -1
    log = EventLog()
    log.append_batch(t, code[k], s, d)
    return log, reference.RefEvents(t, k, s, np.maximum(d, 0), n_ids)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_fold_and_algorithms_match_engine(seed):
    """Windowed views with deletes: the engine's fold against a fold that
    is not its own, and on that fold's alive sets PageRank against the
    benchmark's float64 power iteration, components and degrees against
    NetworkX."""
    log, ref = _adversarial_stream(seed)
    saw_dead_edge = False
    for T in (15, 33, 59):
        view = build_view(log, T)
        for w in (None, 20, 4):
            vm, src, dst = ref.fold(T, w)
            if w is None:
                v_mask, e_mask = view.v_mask, view.e_mask
            else:
                (v_mask,), (e_mask,) = view.window_masks([w])
            assert sorted(view.vids[v_mask]) == \
                np.flatnonzero(vm).tolist()
            got_e = sorted(zip(view.vids[view.e_src[e_mask]].tolist(),
                               view.vids[view.e_dst[e_mask]].tolist()))
            assert got_e == sorted(zip(src.tolist(), dst.tolist()))
            saw_dead_edge |= len(src) < len(ref.us)
            G = nx.DiGraph()
            G.add_nodes_from(np.flatnonzero(vm).tolist())
            G.add_edges_from(zip(src.tolist(), dst.tolist()))

            ranks, _ = bsp.run(PageRank(tol=1e-9, max_steps=200), view,
                               window=w)
            got = np.zeros(ref.n_ids)
            got[view.vids[view.v_mask]] = np.asarray(ranks)[view.v_mask]
            np.testing.assert_allclose(
                got, ref_pagerank.pagerank(vm, src, dst, 200),
                atol=6e-9, rtol=2e-4)

            cc = ConnectedComponents()
            labels, _ = bsp.run(cc, view, window=w)
            sizes = sorted((len(c) for c in nx.connected_components(
                G.to_undirected())), reverse=True)
            got_cc = cc.reduce(labels, view, window=w)
            assert got_cc == {
                "vertices": len(G), "clusters": len(sizes),
                "biggest": sizes[0] if sizes else 0,
                "islands": sizes.count(1),
                "proportion": sizes[0] / len(G) if sizes else 0.0,
                "top5": sizes[:5]}

            deg = DegreeBasic()
            res, _ = bsp.run(deg, view, window=w)
            ind = [x for _, x in G.in_degree()]
            outd = [x for _, x in G.out_degree()]
            assert deg.reduce(res, view, window=w) == {
                "vertices": len(G), "total_in": sum(ind),
                "total_out": sum(outd), "max_in": max(ind, default=0),
                "max_out": max(outd, default=0),
                "avg_degree": (sum(ind) + sum(outd)) / max(len(G), 1)}
    assert saw_dead_edge, "the stream never killed an edge"
