"""SGC's feature propagation (``Y = S^K X`` over a row of features a
vertex): the plain numpy reference of the benchmark
(``benchmark/algorithms/sgc.py``) against ``S^K X`` with dense numpy
matrices, and the program — the hashed features, ``SGC`` on ``bsp``, the
propagation table, the columnar kernel, a served Range and View on the
``hopbatch.delta.sgc`` route — against that reference."""

import json
import time
import urllib.request
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import gen
from benchmark.algorithms import sgc as ref_sgc
from raphtory_tpu.algorithms import SGC
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp
from raphtory_tpu.engine.hopbatch import HopBatchedSGC
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import (AnalysisManager, LiveQuery,
                                       RangeQuery, ViewQuery)
from raphtory_tpu.jobs.rest import RestServer
from raphtory_tpu.ops import propagate

from test_lcc import N_IDS, _columns, _log, _ref, _serve

#: a narrow program for the tests that walk many views: the width is a
#: field, the kernel the same; the served tests run Reddit's 602
PARAMS = {"rounds": 2, "dim": 24, "feature_seed": 5}
WIDE = {"rounds": 2, "dim": 602, "feature_seed": 2017}
VIEWS = [(95, None), (95, 30), (60, 12)]


def _alg(params):
    return {"params": params, "iterations": params["rounds"]}


def _limits(tol=2e-6):
    return {**dict.fromkeys(ref_sgc.COMPARED, 0), "probe_rel_err": tol,
            "col_sum_rel_err": tol, "frob_rel_err": tol}


def _compare(row, r, params):
    want = ref_sgc.reference(*r.fold(row["time"], row["windowsize"]),
                             _alg(params))
    out = ref_sgc.compare(row, want, _limits(), _alg(params))
    assert out["ok"], (row["time"], row["windowsize"], out)
    return want


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_s_to_the_k_times_x_in_dense_matrices(seed):
    """On views that hold a self-loop, a pair joined both ways and a
    vertex deleted and revived, under a window and without one."""
    cols = _columns(seed)
    t, k, s, _ = cols
    r = _ref(cols)
    saw_loop = saw_both = saw_revived = False
    for T, w in VIEWS:
        vm, src, dst = r.fold(T, w)
        alive, deg, Y = ref_sgc.propagate(vm, src, dst, _alg(PARAMS))
        n = len(alive)
        at = {int(v): i for i, v in enumerate(alive)}
        A = np.zeros((n, n))
        for a, b in zip(src.tolist(), dst.tolist()):
            A[at[a], at[b]] = 1.0
        At = A + A.T + np.eye(n)
        d = At.sum(axis=1)
        S = At / np.sqrt(d)[:, None] / np.sqrt(d)[None, :]
        X = ref_sgc.features(alive, PARAMS["dim"],
                             PARAMS["feature_seed"]).astype(np.float64)
        np.testing.assert_array_equal(deg, d.astype(np.int64))
        np.testing.assert_allclose(Y, S @ S @ X, rtol=1e-12, atol=1e-14)
        np.testing.assert_array_equal(
            deg, 1 + np.bincount([at[a] for a in src.tolist()], minlength=n)
            + np.bincount([at[b] for b in dst.tolist()], minlength=n))
        pairs = set(zip(src.tolist(), dst.tolist()))
        saw_loop |= any(a == b and At[at[a], at[a]] == 3 for a, b in pairs)
        saw_both |= any((b, a) in pairs and At[at[a], at[b]] == 2
                        for a, b in pairs if a != b)
        gone = {int(v): int(tt) for tt, kk, v in zip(t, k, s)
                if kk == gen.VDEL and tt < T}
        saw_revived |= any(vm[v] for v in gone)
    assert saw_loop and saw_both and saw_revived


@pytest.mark.parametrize("dim,seed", [(602, 2017), (7, 0), (128, 2**32 - 1)])
def test_hashed_features_are_bit_equal_in_numpy_and_jnp(dim, seed):
    import jax.numpy as jnp

    vids = np.array([0, 1, 2, 999_999, 2**31 - 1, 2**31, 2**32 - 1, 2**32,
                     2**40 + 12345, 7_135_000, -1], np.int64)
    want = ref_sgc.features(vids, dim, seed)
    got = np.asarray(propagate.features(jnp.asarray(vids), dim, seed))
    assert got.dtype == np.float32 and got.shape == (len(vids), dim)
    np.testing.assert_array_equal(got.view(np.uint32), want.view(np.uint32))
    # 24 bits in [-1, 1): whole multiples of 2^-23, no two columns alike
    assert want.min() >= -1.0 and want.max() < 1.0
    assert np.array_equal(want * 2.0 ** 23, np.round(want * 2.0 ** 23))
    if dim > 100:
        assert abs(float(want.mean())) < 0.05
        assert len(np.unique(want[0])) > dim * 0.99


def _bsp_row(log, T, w, params):
    prog = SGC(**params)
    view = build_view(log, T)
    result, steps = bsp.run(prog, view, window=w)
    return {"time": T, "windowsize": w, "steps": int(steps),
            "result": prog.reduce(result, view, window=w)}


@pytest.mark.parametrize("seed,window", [(0, None), (2, 30), (4, 7)])
def test_sgc_on_bsp_equals_the_reference(seed, window):
    """The program as written (sum along both directions, a row of
    features a vertex as its state) on the engine that is not served."""
    cols = _columns(seed)
    log, r = _log(cols), _ref(cols)
    for T in (40, 95):
        row = _bsp_row(log, T, window, PARAMS)
        assert row["steps"] == 2
        want = _compare(row, r, PARAMS)
        assert want["vertices"] > 10 and want["edges"] > 10
    assert SGC(**PARAMS).max_steps == 2 and SGC(rounds=3).max_steps == 3


@pytest.mark.parametrize("hops,windows", [([80], (None,)),
                                          ([70], (100, 30, 12)),
                                          ([60, 90], (100, 30, 12))])
def test_columnar_kind_equals_bsp_column_by_column(hops, windows):
    """C = 1, 3 and 6 columns of one dispatch against ``bsp`` a view: the
    hubs and their degrees exactly, the floats to rounding."""
    cols = _columns(3)
    log = _log(cols)
    hb = HopBatchedSGC(log, **PARAMS)
    out, steps = hb.run(hops, windows)
    out = {key: np.asarray(v) for key, v in out.items()}
    C = len(hops) * len(windows)
    assert int(steps) == 2 and out["probe"].shape == (C, 10, PARAMS["dim"])
    shell = SimpleNamespace(vids=hb.tables.vids)
    for j, T in enumerate(hops):
        for i, w in enumerate(windows):
            col = {key: v[j * len(windows) + i] for key, v in out.items()}
            got = SGC(**PARAMS).reduce(col, shell, window=w)
            want = _bsp_row(log, T, w, PARAMS)["result"]
            assert [r[:2] for r in got["top10"]] == \
                [r[:2] for r in want["top10"]]
            assert (got["vertices"], got["edges"]) == \
                (want["vertices"], want["edges"])
            for key in ("col_sum", "probe", "frob"):
                np.testing.assert_allclose(got[key], want[key], rtol=2e-5,
                                           atol=1e-6)


def _random_columns(t, rng, C):
    """``C`` columns of masks over the tables ``t``: vertices and pairs
    kept with falling odds, a pair only between kept vertices."""
    mv = np.zeros((t.n_pad, C), bool)
    me = np.zeros((t.m_pad, C), bool)
    mv[:t.n] = rng.random((t.n, C)) < np.linspace(1.0, 0.3, C)
    me[:t.m] = (rng.random((t.m, C)) < np.linspace(1.0, 0.2, C)) \
        & mv[t.e_src[:t.m]] & mv[t.e_dst[:t.m]]
    return me, mv


@pytest.mark.parametrize("columns,large", [(4, False), (33, False),
                                           (3, True)])
def test_kernel_equals_the_reference_under_any_masks(monkeypatch, columns,
                                                     large):
    """The kernel alone, past one mask word of columns too, and at the
    step a table of 2^20 rows or more takes."""
    import jax
    import jax.numpy as jnp

    from raphtory_tpu.engine.device_sweep import LogIndex

    if large:
        monkeypatch.setattr(propagate, "LARGE_ROWS", 0)

    t = LogIndex(_log(_columns(5, n_events=2500)).freeze()).tables
    me, mv = _random_columns(t, np.random.default_rng(columns), columns)
    dim = 12
    X = propagate.features(jnp.asarray(t.vids), dim, 9)
    table = propagate.PropagationTable(*map(jnp.asarray, propagate.build_table(
        t.e_src, t.e_dst, t.n_pad)))
    out = jax.jit(propagate.sgc_columns, static_argnums=2)(
        jnp.asarray(me), jnp.asarray(mv), 2, X, table)
    out = {key: np.asarray(v) for key, v in out.items()}
    alg = _alg({"rounds": 2, "dim": dim, "feature_seed": 9})
    prog = SGC(**alg["params"])
    for c in range(columns):
        vm = np.zeros(N_IDS, bool)
        vm[t.uv[mv[:t.n, c]]] = True
        keep = me[:t.m, c]
        src, dst = t.uv[t.e_src[:t.m][keep]], t.uv[t.e_dst[:t.m][keep]]
        row = {"steps": 2, "result": prog.reduce(
            {key: v[c] for key, v in out.items()},
            SimpleNamespace(vids=t.vids))}
        cmp_ = ref_sgc.compare(row, ref_sgc.reference(vm, src, dst, alg),
                               _limits(), alg)
        assert cmp_["ok"], (c, cmp_)


def test_the_table_holds_both_directions_and_one_row_a_vertex_by_receiver():
    """The table is sorted by receiver, holds both directions of every
    pair row and one row a vertex id, each listening to its entity's
    mask, padded to whole steps."""
    from raphtory_tpu.engine.device_sweep import LogIndex

    t = LogIndex(_log(_columns(6, n_events=2500)).freeze()).tables
    tab = propagate.build_table(t.e_src, t.e_dst, t.n_pad)
    to, frm, ent = tab.to, tab.frm, tab.ent
    rows = propagate.table_rows(t.m_pad, t.n_pad)
    assert len(to) == rows and rows % propagate.step_rows(rows) == 0
    assert propagate.step_rows(rows) == propagate.STEP_SMALL
    assert propagate.step_rows(1 << 20) == propagate.STEP_LARGE
    assert propagate.table_rows(3_735_552, 131_072) == 116 * 65_536
    assert np.all(np.diff(to) >= 0)
    assert set(np.unique(to)) == set(range(t.n_pad))
    real = ent < t.m_pad
    assert real.sum() == 2 * t.m_pad
    fwd = (to == t.e_dst[np.minimum(ent, t.m_pad - 1)]) \
        & (frm == t.e_src[np.minimum(ent, t.m_pad - 1)])
    back = (to == t.e_src[np.minimum(ent, t.m_pad - 1)]) \
        & (frm == t.e_dst[np.minimum(ent, t.m_pad - 1)])
    assert np.all((fwd | back)[real])
    own = (ent >= t.m_pad) & (ent < t.m_pad + t.n_pad)
    assert own.sum() == t.n_pad and np.all(to[own] == frm[own])
    assert np.all(to[own] == ent[own] - t.m_pad)
    np.testing.assert_array_equal(
        tab.upto, np.searchsorted(to, np.arange(t.n_pad), "right"))
    assert all(a.dtype == np.int32 for a in tab[:4])
    # 1 / sqrt(d) for every d~ the table allows, rounded from float64
    most = int(np.diff(tab.upto, prepend=0).max())
    assert tab.inv_sqrt.dtype == np.float32 and len(tab.inv_sqrt) > most
    assert len(tab.inv_sqrt) & (len(tab.inv_sqrt) - 1) == 0
    d = np.arange(1, len(tab.inv_sqrt))
    np.testing.assert_array_equal(
        tab.inv_sqrt[1:], (1.0 / np.sqrt(d)).astype(np.float32))
    assert tab.inv_sqrt[0] == 0


def test_a_column_walks_its_alive_rows_alone():
    """``_walked``: the alive rows come first and in the table's order,
    the trip count covers exactly them, what follows adds nothing and
    keeps the receivers sorted — and a column that keeps almost nothing
    walks almost nothing."""
    import jax
    import jax.numpy as jnp

    from raphtory_tpu.engine.device_sweep import LogIndex

    t = LogIndex(_log(_columns(6, n_events=4000)).freeze()).tables
    host = propagate.build_table(t.e_src, t.e_dst, t.n_pad)
    tab = propagate.PropagationTable(*map(jnp.asarray, host))
    rows = len(host.to)
    rng = np.random.default_rng(0)
    for odds in (1.0, 0.5, 0.02, 0.0):
        alive = (rng.random(rows) < odds) & (host.ent < t.m_pad + t.n_pad)
        frm, to, steps = jax.jit(propagate._walked)(tab, jnp.asarray(alive))
        frm, to, k = np.asarray(frm), np.asarray(to), int(alive.sum())
        assert int(steps) == -(-k // propagate.step_rows(rows))
        np.testing.assert_array_equal(to[:k], host.to[alive])
        np.testing.assert_array_equal(frm[:k], host.frm[alive])
        assert np.all(frm[k:] == t.n_pad) and np.all(to[k:] == t.n_pad - 1)
        assert np.all(np.diff(to) >= 0)
    assert k == 0 and int(steps) == 0


def test_a_sweep_advances_incrementally_on_the_resident_base():
    """Two batches of one engine, the second fed by deltas on the state
    the first left on the device, equal fresh engines' answers."""
    log = _log(_columns(7))
    hb = HopBatchedSGC(log, **PARAMS)
    first, _ = hb.run([40, 60], (30,))
    second, _ = hb.run([80, 95], (30,))
    fresh, _ = HopBatchedSGC(log, **PARAMS).run([80, 95], (30,))
    for key in ("top_idx", "top_deg", "vertices", "edges"):
        np.testing.assert_array_equal(np.asarray(second[key]),
                                      np.asarray(fresh[key]))
    for key in ("col_sum", "probe", "col_sq"):
        np.testing.assert_allclose(np.asarray(second[key]),
                                   np.asarray(fresh[key]), rtol=1e-6)
    assert not np.allclose(np.asarray(first["col_sum"]),
                           np.asarray(second["col_sum"]))  # the window moved


def _rest(port, path, body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=None if body is None else json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as r:
        return json.loads(r.read())


def _rest_rows(port, path, body):
    jid = _rest(port, path, {"analyserName": "SGC", "params": WIDE,
                             "explain": 1, **body})["jobID"]
    deadline = time.monotonic() + 240
    while time.monotonic() < deadline:
        doc = _rest(port, f"/AnalysisResults?jobID={jid}")
        if doc["status"] not in ("pending", "running"):
            break
        time.sleep(0.05)
    assert doc["status"] == "done", doc.get("error")
    return doc


def test_a_range_and_a_view_over_rest_equal_the_reference_at_602_columns():
    cols = _columns(1)
    log, r = _log(cols), _ref(cols)
    srv = RestServer(AnalysisManager(TemporalGraph(log)), port=0).start()
    try:
        doc = _rest_rows(srv.port, "/RangeAnalysisRequest", {
            "start": 60, "end": 90, "jump": 30, "windowType": "batched",
            "windowSet": [100, 30, 12]})
        assert len(doc["results"]) == 6
        for row in doc["results"]:
            assert row["steps"] == 2 and row["result"]["dim"] == 602
            assert len(row["result"]["col_sum"]) == 602
            assert np.asarray(row["result"]["probe"]).shape == (10, 602)
            _compare(row, r, WIDE)
        dev = doc["ledger"]["device"]
        assert list(dev["kernels"]) == ["hopbatch.delta.sgc"]
        assert (dev["chunks"], dev["columns"], dev["chunk_rule"]) == \
            (1, 6, "one_dispatch")
        view = _rest_rows(srv.port, "/ViewAnalysisRequest", {
            "timestamp": 80, "windowType": "batched", "windowSet": [100, 20]})
        assert [row["windowsize"] for row in view["results"]] == [100, 20]
        for row in view["results"]:
            _compare(row, r, WIDE)
        assert list(view["ledger"]["device"]["kernels"]) == \
            ["hopbatch.delta.sgc"]
    finally:
        srv.stop()


@pytest.mark.parametrize("seed,windows", [(0, (100, 30, 12)), (2, None)])
def test_served_range_rides_the_columnar_route(seed, windows):
    cols = _columns(seed)
    log, r = _log(cols), _ref(cols)
    q = RangeQuery(start=60, end=90, jump=30, windows=windows)
    job, spans = _serve(log, registry.resolve("SGC", PARAMS), q)
    names = {s["name"] for s in spans}
    assert "hop.compute" in names and "sweep.columnar" in names
    assert not names & {"bsp.dispatch", "snapshot.fold"}
    (compute,) = [s for s in spans if s["name"] == "hop.compute"]
    assert compute["args"]["kind"] == "sgc"        # one dispatch a request
    assert compute["args"]["combine"] == "rows"
    (build,) = [s for s in spans if s["name"] == "engine.build"]
    assert build["args"]["engine"] == "HopBatchedSGC"
    assert build["args"]["features"] == "built"
    (sweep,) = [s for s in spans if s["name"] == "sweep.columnar"]
    n_w = len(windows or (None,))
    assert (sweep["args"]["chunks"], sweep["args"]["columns"],
            sweep["args"]["chunk_rule"]) == (1, 2 * n_w, "one_dispatch")
    led = job.ledger.as_dict()
    assert list(led["device"]["kernels"]) == ["hopbatch.delta.sgc"]
    rows = job.results_snapshot()
    assert len(rows) == 2 * n_w
    # the rows of A + A^T + I alive in each column, once a round: what the
    # device walked, not the table's size
    assert led["device"]["feature_rows"] == PARAMS["rounds"] * sum(
        2 * row["result"]["edges"] + row["result"]["vertices"]
        for row in rows)
    assert led["device"]["feature_rows"] < \
        build["args"]["m_pad"] * 2 * PARAMS["rounds"] * 2 * n_w
    for row in rows:
        assert row["steps"] == 2
        _compare(row, r, PARAMS)


def test_features_and_table_are_made_once_a_log_and_for_sgc_alone():
    from raphtory_tpu.obs import device as obs_device

    cols = _columns(8)
    log = _log(cols)
    graph = TemporalGraph(log)
    q = RangeQuery(start=60, end=90, jump=30, windows=(30,))
    _, spans = _serve(log, registry.resolve("PageRank", {}), q, graph)
    (build,) = [s for s in spans if s["name"] == "engine.build"]
    assert "features" not in build["args"]
    held = []
    for params in (PARAMS, PARAMS, {**PARAMS, "feature_seed": 6}, PARAMS):
        _, spans = _serve(log, registry.resolve("SGC", params), q, graph)
        (build,) = [s for s in spans if s["name"] == "engine.build"]
        held.append(build["args"]["features"])
    # another seed is another block (one is kept), never another table
    assert held == ["built", "held", "built", "built"]
    rows = [row for row in obs_device.RESIDENT.snapshot()["buffers"]
            if row["kind"] == "feature_tables"]
    assert rows and all(row["nbytes"] > 0 for row in rows)


def test_engines_of_two_seeds_on_one_log_each_hold_their_own_features():
    """Built at once on separate threads, as two jobs are: the cache
    keeps one block, and an engine never takes the other seed's."""
    import threading

    log = _log(_columns(3))
    HopBatchedSGC(log, **PARAMS)        # the table is held from here on
    seeds = [11, 12, 13, 14] * 3
    start = threading.Barrier(len(seeds))
    made = [None] * len(seeds)

    def build(i):
        start.wait()
        made[i] = HopBatchedSGC(log, **{**PARAMS, "feature_seed": seeds[i]})

    threads = [threading.Thread(target=build, args=(i,))
               for i in range(len(seeds))]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    for hb, seed in zip(made, seeds):
        t = hb.tables
        want = ref_sgc.features(t.uv, PARAMS["dim"], seed)
        np.testing.assert_array_equal(np.asarray(hb._features)[: t.n], want)
        assert hb._table is made[0]._table      # never another table


def test_a_view_is_one_dispatch_and_live_and_a_mesh_name_the_route():
    import jax

    from raphtory_tpu.parallel import sharded

    cols = _columns(4)
    log, r = _log(cols), _ref(cols)
    job, spans = _serve(log, registry.resolve("SGC", PARAMS),
                        ViewQuery(80, windows=(100, 20)))
    (compute,) = [s for s in spans if s["name"] == "hop.compute"]
    assert compute["args"]["kind"] == "sgc" and compute["args"]["cols"] == 2
    assert "bsp.dispatch" not in {s["name"] for s in spans}
    for row in job.results_snapshot():
        _compare(row, r, PARAMS)
    job, spans = _serve(log, registry.resolve("SGC", PARAMS),
                        LiveQuery(repeat=0.01, max_runs=1), ok=False)
    assert job.status == "failed" and "hopbatch.delta.sgc" in job.error
    assert "bsp.dispatch" not in {s["name"] for s in spans}
    mesh = sharded.make_mesh(2, 1, devices=jax.devices()[:2])
    job = AnalysisManager(TemporalGraph(log), mesh=mesh).submit(
        registry.resolve("SGC", PARAMS),
        RangeQuery(start=60, end=90, jump=30, windows=(30,)))
    assert job.wait(120)
    assert job.status == "failed" and "hopbatch.delta.sgc" in job.error
