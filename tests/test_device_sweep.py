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

from raphtory_tpu.ops import gather as gather_ops
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
    text = runner.fn.lower(*_view_args(ds, k)).compile().as_text()
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


def _superstep_bodies(fn, *args):
    """The equations inside each ``while`` body of the program ``fn(*args)``
    traces to that holds a ``combine.*`` scope: the superstep loops. What
    is traced is what every backend lowers."""
    for eqn in _walk(jax.make_jaxpr(fn)(*args).jaxpr):
        if eqn.primitive.name != "while":
            continue
        inside = list(_walk(eqn.params["body_jaxpr"].jaxpr))
        if any("combine." in str(e.source_info.name_stack) for e in inside):
            yield inside


def _superstep_loops(fn, *args):
    """``(primitive names, combine.* scopes)`` of each superstep loop."""
    return [({e.primitive.name for e in inside},
             {m for e in inside for m in re.findall(
                 r"combine\.\w+", str(e.source_info.name_stack))})
            for inside in _superstep_bodies(fn, *args)]


def _view_args(ds, k):
    return (*ds._bufs, ds.vids, ds.e_src, ds.e_dst,
            jnp.asarray(0, jnp.int64), jnp.zeros((k,), jnp.int64))


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
    (prims, scopes), = _superstep_loops(runner.fn, *_view_args(ds, k))
    assert scopes == {"combine.gather", "combine.scan", "combine.pick"}
    assert not {p for p in prims if p.startswith("scatter")}, prims
    assert not prims & {"cummax", "cumsum", "while", "sort"}, prims


@dataclasses.dataclass(frozen=True)
class RowSharePageRank(PageRank):
    """``PageRank`` with ``share`` kept as a row a vertex (``[n, 2]``: the
    share and the rank beside it) — a state leaf with a trailing dimension,
    whose gather is a row gather as it stands."""

    def init(self, ctx):
        st = super().init(ctx)
        return {"rank": st["rank"],
                "share": jnp.stack([st["share"], st["rank"]], axis=-1)}

    def message(self, src_state, edge):
        return src_state["share"][:, 0]

    def update(self, state, agg, ctx):
        new, votes = super().update(state, agg, ctx)
        return {"rank": new["rank"],
                "share": jnp.stack([new["share"], new["rank"]], axis=-1)
                }, votes


@dataclasses.dataclass(frozen=True)
class RowOnlyPageRank(PageRank):
    """``PageRank`` whose whole state is one ``[n, 2]`` leaf (the share and
    the rank beside it): no leaf of one element a vertex, nothing to pack."""

    def init(self, ctx):
        st = super().init(ctx)
        return jnp.stack([st["share"], st["rank"]], axis=-1)

    def message(self, src_state, edge):
        return src_state[:, 0]

    def update(self, state, agg, ctx):
        new, votes = super().update(
            {"share": state[:, 0], "rank": state[:, 1]}, agg, ctx)
        return jnp.stack([new["share"], new["rank"]], axis=-1), votes

    def finalize(self, state, ctx):
        return state[:, 1]


_I64_MAX = np.iinfo(np.int64).max


@dataclasses.dataclass(frozen=True)
class SealedLabels(ConnectedComponents):
    """Min-label propagation over the leaves ``TaintTracking`` carries: an
    int64 a vertex (8 bytes an element of a picked row, not 4) and a bool
    beside it that ``message`` reads too — a sealed vertex emits nothing."""

    def init(self, ctx):
        label = super().init(ctx).astype(jnp.int64)
        return {"label": jnp.where(ctx.v_mask, label, _I64_MAX),
                "sealed": ctx.v_mask & (label % 3 == 0)}

    def message(self, src_state, edge):
        return jnp.where(src_state["sealed"], _I64_MAX, src_state["label"])

    def update(self, state, agg, ctx):
        new = jnp.where(ctx.v_mask, jnp.minimum(state["label"], agg),
                        _I64_MAX)
        return {"label": new, "sealed": state["sealed"]}, new == state["label"]

    def finalize(self, state, ctx):
        return state["label"]


def _loop_gathers(fn, *args):
    """``(gathers, integer div / rem)`` inside the superstep loop of the
    program ``fn(*args)`` traces to: the shapes of the operand and of the
    result of every ``gather`` under the ``combine.gather`` scope, and
    the integer divisions (name, shape) anywhere in the loop's body."""
    (inside,) = _superstep_bodies(fn, *args)
    gathers = [(e.invars[0].aval.shape, e.outvars[0].aval.shape)
               for e in inside if e.primitive.name == "gather"
               and "combine.gather" in str(e.source_info.name_stack)]
    divs = [(e.primitive.name, e.outvars[0].aval.shape) for e in inside
            if e.primitive.name in ("div", "rem") and jnp.issubdtype(
                e.outvars[0].aval.dtype, jnp.integer)]
    return gathers, divs


@pytest.mark.parametrize("program,windows", [
    (PageRank(max_steps=20, tol=0.0), [30]),
    (PageRank(max_steps=20, tol=0.0), [100, 30, 7]),
    (ConnectedComponents(max_steps=50), [30]),
    (SealedLabels(max_steps=50), [100, 30, 7]),
    (RowSharePageRank(max_steps=20, tol=0.0), [100, 30, 7]),
    (RowOnlyPageRank(max_steps=20, tol=0.0), [30]),
], ids=["pagerank-k1", "pagerank-k3", "cc-k1", "int64-and-bool-k3",
        "row-leaf-k3", "row-only-k1"])
def test_a_one_element_leaf_is_gathered_packed_inside_the_loop(program,
                                                              windows):
    """A state leaf of one element a vertex is never gathered element by
    element (docs/KERNELS.md): inside the superstep loop the
    ``combine.gather`` scope gathers rows of the ``[k * n / P, P]`` view,
    never out of the flat ``[k * n]`` table, and ``id // P`` / ``id % P``
    are formed before the loop, beside the scan's plan. A leaf with a
    trailing dimension is a row gather already and stays as it is, and
    ``state_pack`` says 1 for a program that has no other."""
    ds = _churned_sweep()
    k = len(windows)
    P = bsp.state_pack(program, ds.n_pad, k)
    runner = _compiled_run(program, ds.n_pad, ds.m_pad, k,
                           np.dtype(ds.tdtype).name)
    gathers, divs = _loop_gathers(runner.fn, *_view_args(ds, k))
    flat = k * ds.n_pad
    # every leaf is gathered in the traced program (the compiler drops
    # what ``message`` does not read): ``rank`` is one element a vertex
    packed = ((flat // P, P), (k * ds.m_pad, P))
    rows = ((flat, 2), (k * ds.m_pad, 2))
    if isinstance(program, RowOnlyPageRank):
        assert P == 1 and set(gathers) == {rows}
    elif isinstance(program, RowSharePageRank):
        assert P >= 2 and set(gathers) == {packed, rows}
    else:
        assert P >= 2 and gathers and set(gathers) == {packed}
    # (the scan divides its few block carries' offsets; never the ids)
    assert not [d for d in divs if d[1] == (k * ds.m_pad,)], divs


@pytest.mark.parametrize("program,windows,itemsize", [
    (PageRank(max_steps=20, tol=0.0), [100, 30, 7], 4),
    (SealedLabels(max_steps=50), [30], 8),
], ids=["float32-k3", "int64-and-bool-k1"])
def test_the_picked_rows_are_read_a_tile_of_ids_at_a_time(
        monkeypatch, program, windows, itemsize):
    """The picked rows ``[ids, P]`` are the dispatch's largest temporary,
    ``LANES`` elements a gathered id: past ``PACKED_ROWS_BYTES`` of them a
    leaf is read a tile of ids at a time — an int64 leaf at half the ids a
    float32 one takes, a bool at four times — and no gather of the loop
    reads more than its leaf's tile."""
    ds = _churned_sweep()
    k = len(windows)
    ids = k * ds.m_pad
    per_tile = ids // 3 - 8         # float32 ids: three tiles and a rest
    monkeypatch.setattr(gather_ops, "PACKED_ROWS_BYTES",
                        per_tile * gather_ops.LANES * 4)
    assert gather_ops.rows_tile(ids, 4) == per_tile
    assert gather_ops.rows_tile(ids, 8) == per_tile // 2
    assert gather_ops.rows_tile(ids, 1) == ids
    _compiled_run.cache_clear()
    try:
        runner = _compiled_run(program, ds.n_pad, ds.m_pad, k,
                               np.dtype(ds.tdtype).name)
        (inside,) = _superstep_bodies(runner.fn, *_view_args(ds, k))
    finally:
        _compiled_run.cache_clear()
    P = bsp.state_pack(program, ds.n_pad, k)
    got = {(str(e.invars[0].aval.dtype), e.outvars[0].aval.shape)
           for e in inside if e.primitive.name == "gather"
           and e.invars[0].aval.shape == (k * ds.n_pad // P, P)
           and e.outvars[0].aval.ndim == 2}
    tile = per_tile * 4 // itemsize
    want = {(f"{'float' if itemsize == 4 else 'int'}{8 * itemsize}", shape)
            for shape in ((tile, P), (ids - ids // tile * tile, P))}
    if itemsize == 8:
        want.add(("bool", (ids, P)))
    assert got == want


@pytest.fixture
def gather_form(monkeypatch):
    """Programs traced after ``gather_form("flat")`` see ``ONE_COLUMN_PACK``
    1, the flat gather; after ``gather_form("tiled")`` a budget of picked
    rows that 150 float32 ids fill. Both are read at trace time, so the
    compiled programs go too."""
    def clear():
        _compiled_run.cache_clear()
        bsp._compiled_runner.cache_clear()
        bsp.state_pack.cache_clear()

    def switch(form):
        if form == "flat":
            monkeypatch.setattr(gather_ops, "ONE_COLUMN_PACK", 1)
        else:
            monkeypatch.setattr(gather_ops, "PACKED_ROWS_BYTES",
                                150 * gather_ops.LANES * 4)
        clear()
    yield switch
    monkeypatch.undo()
    clear()


@pytest.mark.parametrize("program,windows", [
    (PageRank(max_steps=20, tol=0.0), [30]),
    (PageRank(max_steps=20, tol=0.0), [100, 30, 7]),
    (ConnectedComponents(max_steps=50), [100, 30]),
    (DegreeBasic(), [100, 30]),
    (SealedLabels(max_steps=50), [100, 30]),
    (RowSharePageRank(max_steps=20, tol=0.0), [100, 30]),
], ids=["pagerank-k1", "pagerank-k3", "cc", "degree", "int64-and-bool",
        "row-leaf"])
def test_packed_gather_is_a_selection_of_the_flat_ones_elements(
        gather_form, program, windows):
    """128 vertices a table row or one, all the ids at once or a tile of
    them at a time: the same elements, selected — the results are equal,
    not close, for float, integer and bool leaves, on the resident View
    and on the cold (host-mask) one."""
    log = random_log(np.random.default_rng(11), n_events=1500, n_ids=300,
                     t_span=80)
    view = build_view(log, 60)
    k = len(windows)

    def answers():
        ds = DeviceSweep(log)
        got, steps = ds.run(program, 60, windows=windows)
        cold, csteps = bsp.run(program, view, windows=windows)
        return (jax.tree_util.tree_leaves((got, cold)),
                int(np.max(steps)), int(np.max(csteps)))

    # (degree never leaves superstep 0: it keeps no state to gather)
    assert (bsp.state_pack(program, view.n_pad, k) >= 2) == (
        not isinstance(program, DegreeBasic))
    assert gather_ops.rows_tile(k * len(view.e_src), 4) == k * len(view.e_src)
    packed = answers()
    gather_form("tiled")
    assert gather_ops.rows_tile(k * len(view.e_src), 4) == 150
    tiled = answers()
    gather_form("flat")
    assert bsp.state_pack(program, view.n_pad, k) == 1
    flat = answers()
    assert packed[1:] == tiled[1:] == flat[1:]
    assert any(np.asarray(a).any() for a in packed[0])
    for a, b, c in zip(packed[0], tiled[0], flat[0]):
        assert np.array_equal(np.asarray(a), np.asarray(c))
        assert np.array_equal(np.asarray(b), np.asarray(c))


def _spans(name, run):
    """The arguments of the ``name`` spans that ``run()`` records."""
    from raphtory_tpu.obs.trace import TRACER

    was = TRACER.enabled
    TRACER.enable()
    try:
        before = TRACER.recorded
        run()
        events = TRACER.recent(TRACER.recorded - before)
    finally:
        (TRACER.enable if was else TRACER.disable)()
    return [e["args"] for e in events if e["name"] == name]


@pytest.mark.parametrize("program,windows,packs", [
    (PageRank(max_steps=5, tol=0.0), [30], True),
    (PageRank(max_steps=5, tol=0.0), [100, 30, 7], True),
    (RowOnlyPageRank(max_steps=5, tol=0.0), [30], False),
], ids=["k1", "k3", "row-only"])
def test_hop_compute_says_how_many_vertices_a_table_row_held(program,
                                                            windows, packs):
    """``hop.compute`` (the resident View) and ``bsp.dispatch`` (the cold
    one) carry ``gather_pack``: a trace says per dispatch what the program
    gathered its one-element leaves with — 1 where it has none."""
    log = random_log(np.random.default_rng(11), n_events=600, n_ids=40,
                     t_span=80)
    ds, view, k = DeviceSweep(log), build_view(log, 60), len(windows)
    (span,) = _spans("hop.compute",
                     lambda: ds.run(program, 60, windows=windows))
    assert span["engine"] == "device_sweep" and span["combine"] == "scan"
    assert span["gather_pack"] == bsp.state_pack(program, ds.n_pad, k)
    assert (span["gather_pack"] >= 2) == packs
    (span,) = _spans("bsp.dispatch",
                     lambda: bsp.run(program, view, windows=windows))
    assert span["gather_pack"] == bsp.state_pack(program, view.n_pad, k)
    assert (span["gather_pack"] >= 2) == packs


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
    holds one gather fewer than the form that divides two leaves per edge.
    (A log of 300 ids: a state table of one row, as 40 ids make, is no
    gather at all once compiled.)"""
    ds = DeviceSweep(random_log(np.random.default_rng(11), n_events=1500,
                                n_ids=300, t_span=80))
    ds.advance(60)
    assert ds.n_pad > gather_ops.LANES
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
