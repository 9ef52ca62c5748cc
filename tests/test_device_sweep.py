"""DeviceSweep must match the per-view bsp path program-for-program.

The device-resident sweep runs in the GLOBAL dense vertex space while
``bsp.run`` over ``build_view`` runs per-view local — results are compared
vid-by-vid (and for ConnectedComponents via the representative vid each
label decodes to, which is the component's minimum id in both spaces).
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raphtory_tpu.algorithms import ConnectedComponents, DegreeBasic, PageRank
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp, hopbatch
from raphtory_tpu.engine.device_sweep import (DeviceSweep, _compiled_run,
                                              supported)

from raphtory_tpu.ops.segment import SCAN_MAX_COLUMNS

from test_sweep import random_log


def _view_dict(view, values, window=None):
    mask = (np.asarray(view.v_mask) if window is None
            else view.window_masks([window])[0][0])
    vals = np.asarray(values)
    return {int(v): vals[i] for i, v in enumerate(view.vids) if mask[i]}


def _dev_dict(ds, values, vid_set):
    vals = np.asarray(values)
    pos = np.searchsorted(ds.uv, sorted(vid_set))
    return {int(ds.uv[p]): vals[p] for p in pos}


@pytest.mark.parametrize("seed", [0, 3, 8])
def test_pagerank_matches_view_path(seed):
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_events=600, n_ids=40, t_span=80)
    ds = DeviceSweep(log)
    windows = [100, 30, 7]
    for T in [10, 35, 36, 60, 79]:
        pr = PageRank(max_steps=20, tol=1e-7)
        got, _ = ds.run(pr, T, windows=windows)
        view = build_view(log, T)
        want, _ = bsp.run(pr, view, windows=windows)
        for i, w in enumerate(windows):
            vd = _view_dict(view, want[i], window=w)
            dd = _dev_dict(ds, got[i], vd.keys())
            assert set(vd) == set(dd)
            for vid in vd:
                assert vd[vid] == pytest.approx(dd[vid], abs=1e-5), (T, w, vid)


@pytest.mark.parametrize("seed", [1, 5])
def test_degree_and_cc_match_view_path(seed):
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_events=500, n_ids=30, t_span=60)
    ds = DeviceSweep(log)
    for T in [12, 30, 59]:
        view = build_view(log, T)

        deg = DegreeBasic()
        got, _ = ds.run(deg, T)
        want, _ = bsp.run(deg, view)
        for key in ("in", "out"):
            vd = _view_dict(view, want[key])
            dd = _dev_dict(ds, got[key], vd.keys())
            assert vd == dd, (T, key)

        cc = ConnectedComponents(max_steps=50)
        got, _ = ds.run(cc, T, window=25)
        want, _ = bsp.run(cc, view, window=25)
        # labels are indices in different spaces; both decode to the
        # component's minimum vid — compare representatives per vertex
        vmask = view.window_masks([25])[0][0]
        reps_view = {int(view.vids[i]): int(view.vids[int(l)])
                     for i, l in enumerate(np.asarray(want)) if vmask[i]}
        dev_lab = np.asarray(got)
        pos = np.searchsorted(ds.uv, sorted(reps_view))
        reps_dev = {int(ds.uv[p]): int(ds.uv[int(dev_lab[p])]) for p in pos}
        assert reps_view == reps_dev


def test_multi_chunk_delta_application():
    """Force n_chunks >= 2 on both the vertex and edge side: shrunken chunk
    capacities must produce results identical to the single-chunk path."""
    rng = np.random.default_rng(9)
    log = random_log(rng, n_events=800, n_ids=60, t_span=100)
    pr = PageRank(max_steps=10, tol=1e-7)
    ref = DeviceSweep(log)
    ds = DeviceSweep(log)
    ds.cap_v, ds.cap_e = 8, 16  # far below any real delta size
    for T in [20, 21, 50, 99]:
        got, _ = ds.run(pr, T, windows=[200, 40])
        want, _ = ref.run(pr, T, windows=[200, 40])
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=1e-6)


def test_unsupported_program_raises():
    from raphtory_tpu.algorithms import SSSP

    log = random_log(np.random.default_rng(2), n_events=100)
    ds = DeviceSweep(log)
    sssp = SSSP(seeds=(0,), weight_prop="weight")
    assert not supported(sssp)
    with pytest.raises(ValueError):
        ds.run(sssp, 10)


def test_times_must_ascend_and_repeat_ok():
    log = random_log(np.random.default_rng(4), n_events=200)
    ds = DeviceSweep(log)
    pr = PageRank(max_steps=5)
    ds.run(pr, 20)
    ds.run(pr, 20)  # same time: no-op advance
    with pytest.raises(ValueError):
        ds.advance(10)


def test_wide_timestamps_use_i64_path():
    """Times beyond int32 keep the resident state in i64 and still match
    the per-view path (the narrow-dtype optimisation must be semantics-free
    in both modes)."""
    from raphtory_tpu.core.events import EventLog

    base = 3_000_000_000  # > int32 max
    log = EventLog()
    log.add_edge(base + 10, 1, 2)
    log.add_edge(base + 20, 2, 3)
    log.add_edge(base + 500, 3, 1)
    ds = DeviceSweep(log)
    assert ds.tdtype == np.int64
    pr = PageRank(max_steps=10, tol=1e-8)
    for T in (base + 15, base + 600):
        got, _ = ds.run(pr, T, windows=[1000, 8])
        view = build_view(log, T)
        want, _ = bsp.run(pr, view, windows=[1000, 8])
        for i in range(2):
            vd = _view_dict(view, want[i], window=[1000, 8][i])
            dd = _dev_dict(ds, got[i], vd.keys())
            for vid in vd:
                assert vd[vid] == pytest.approx(dd[vid], abs=1e-6)


def test_empty_log_and_pre_history_time():
    from raphtory_tpu.core.events import EventLog

    log = EventLog()
    log.add_edge(100, 1, 2)
    ds = DeviceSweep(log)
    got, _ = ds.run(PageRank(max_steps=5), 5)  # before any event
    assert float(np.asarray(got).sum()) == pytest.approx(0.0)
    got, _ = ds.run(PageRank(max_steps=5), 150)
    assert float(np.asarray(got).sum()) == pytest.approx(1.0, abs=1e-4)


@dataclasses.dataclass(frozen=True)
class TwoLeafPageRank(PageRank):
    """The per-edge form: ``message`` reads two state leaves and divides once
    an edge. Kept here as the reference ``PageRank``'s per-vertex share is
    held to, bit for bit."""

    def init(self, ctx):
        return {"rank": super().init(ctx)["rank"],
                "out_deg": ctx.out_deg.astype(jnp.float32)}

    def message(self, src_state, edge):
        return src_state["rank"] / jnp.maximum(src_state["out_deg"], 1.0)

    def update(self, state, agg, ctx):
        new, votes = super().update(state, agg, ctx)
        return {"rank": new["rank"], "out_deg": state["out_deg"]}, votes


def _compiled_gathers(ds, program, k):
    """Gather ops in the program a ``k``-window View of ``ds`` compiles to."""
    runner = _compiled_run(program, ds.n_pad, ds.m_pad, k,
                           np.dtype(ds.tdtype).name)
    args = (*ds._bufs, ds.vids, ds.e_src, ds.e_dst,
            jnp.asarray(0, jnp.int64), jnp.zeros((k,), jnp.int64))
    text = runner.fn.lower(*args).compile().as_text()
    return len(re.findall(r" gather\(", text))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else [v]):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _superstep_loops(fn, *args):
    """``(primitive names, combine.* scopes)`` of each ``while`` body of
    the program ``fn(*args)`` traces to that holds a ``combine.*`` scope:
    the superstep loops. What is traced is what every backend lowers."""
    loops = []
    for eqn in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name != "while":
            continue
        inside = list(_walk(eqn.params["body_jaxpr"].jaxpr))
        scopes = {m for e in inside for m in re.findall(
            r"combine\.\w+", str(e.source_info.name_stack))}
        if scopes:
            loops.append(({e.primitive.name for e in inside}, scopes))
    return loops


def _delta_pagerank_args(n_pad, m_pad, H, W, U):
    S, i32, tdt = jax.ShapeDtypeStruct, jnp.int32, jnp.int32
    delta = (S((H, U), i32), S((H, U), tdt), S((H, U), bool))
    return (S((m_pad,), i32), S((m_pad,), i32),
            S((m_pad,), tdt), S((m_pad,), bool),
            S((n_pad,), tdt), S((n_pad,), bool), *delta, *delta,
            S((H * W,), jnp.int64), S((H * W,), jnp.int64))


@pytest.mark.parametrize("H,W", [(2, 3), (1, 1)], ids=["C6", "C1"])
def test_columnar_pagerank_superstep_scans_and_never_scatters(H, W):
    """Inside the superstep loop of ``hopbatch.delta.pagerank`` the sum at
    the destination is a segmented scan and one gather (the three
    ``combine.*`` scopes): no scatter over the m rows, and what depends on
    ``e_dst`` alone (``ends`` / ``pos``: a running maximum, a binary
    search) is computed outside it. The one scatter a dispatch that is
    left is ``out_deg`` at the source, before the loop."""
    n_pad, m_pad, U = 256, 2048, 256
    runner = hopbatch._compiled_delta(
        "pagerank", n_pad, m_pad, H, W, U, U, "int32", False,
        (0.85, 0.0, 20), tile_budget=hopbatch._tile_budget_bytes())
    (prims, scopes), = _superstep_loops(
        runner.fn, *_delta_pagerank_args(n_pad, m_pad, H, W, U))
    assert scopes == {"combine.gather", "combine.scan", "combine.pick"}
    assert not {p for p in prims if p.startswith("scatter")}, prims
    assert not prims & {"cummax", "cumsum", "while", "sort"}, prims


@pytest.mark.parametrize("windows", [[30], [100, 30, 7]], ids=["k1", "k3"])
def test_resident_pagerank_superstep_scans_and_never_scatters(windows):
    """The same for ``device_sweep.superstep.PageRank``: ``make_mask_runner``
    computes ``ends`` / ``pos`` from ``flat_dst`` beside ``in_deg``, before
    the loop, and hands them to ``segment_combine``."""
    ds = _churned_sweep()
    k = len(windows)
    runner = _compiled_run(PageRank(max_steps=20, tol=0.0), ds.n_pad,
                           ds.m_pad, k, np.dtype(ds.tdtype).name)
    args = (*ds._bufs, ds.vids, ds.e_src, ds.e_dst,
            jnp.asarray(0, jnp.int64), jnp.zeros((k,), jnp.int64))
    (prims, scopes), = _superstep_loops(runner.fn, *args)
    assert scopes == {"combine.gather", "combine.scan", "combine.pick"}
    assert not {p for p in prims if p.startswith("scatter")}, prims
    assert not prims & {"cummax", "cumsum", "while", "sort"}, prims


def test_a_wide_columnar_dispatch_keeps_the_scatter():
    """Past ``SCAN_MAX_COLUMNS`` columns the scatter costs less than the
    scan (docs/KERNELS.md): the program adapts on the shape."""
    H, W = 4, SCAN_MAX_COLUMNS // 4 + 1
    n_pad, m_pad, U = 256, 2048, 256
    runner = hopbatch._compiled_delta(
        "pagerank", n_pad, m_pad, H, W, U, U, "int32", False,
        (0.85, 0.0, 20), tile_budget=hopbatch._tile_budget_bytes())
    (prims, scopes), = _superstep_loops(
        runner.fn, *_delta_pagerank_args(n_pad, m_pad, H, W, U))
    assert scopes == {"combine.gather"} and "scatter-add" in prims


PER_EDGE_WINDOWS = [[30], [100, 30, 7]]


def _churned_sweep():
    """A resident sweep over a seeded log with vertex and edge deletes."""
    return DeviceSweep(random_log(np.random.default_rng(11), n_events=600,
                                  n_ids=40, t_span=80))


@pytest.mark.parametrize("windows", PER_EDGE_WINDOWS, ids=["k1", "k3"])
def test_pagerank_superstep_gathers_one_leaf_an_edge(windows):
    """A superstep costs per edge row touched (docs/KERNELS.md): PageRank's
    ``message`` reads one per-vertex share, so its compiled View program
    holds one gather fewer than the form that divides two leaves per edge."""
    ds = _churned_sweep()
    ds.advance(60)
    k = len(windows)
    one = _compiled_gathers(ds, PageRank(max_steps=20, tol=0.0), k)
    two = _compiled_gathers(ds, TwoLeafPageRank(max_steps=20, tol=0.0), k)
    assert one == two - 1, (one, two)


@pytest.mark.parametrize("windows", PER_EDGE_WINDOWS, ids=["k1", "k3"])
def test_pagerank_share_is_bit_identical_to_per_edge_division(windows):
    """``share = rank / max(out_deg, 1)`` once a vertex is the same division
    of the same operands as once an edge: the ranks are equal, not close. A
    reciprocal multiply in its place would fail here."""
    ds = _churned_sweep()
    for T in [35, 60, 79]:
        got, gs = ds.run(PageRank(max_steps=20, tol=0.0), T, windows=windows)
        want, ws = ds.run(TwoLeafPageRank(max_steps=20, tol=0.0), T,
                          windows=windows)
        assert int(gs) == int(ws) == 20
        assert np.asarray(got).any()
        assert np.array_equal(np.asarray(got), np.asarray(want)), T
