"""CDLP (LDBC Graphalytics' community detection): the program on ``bsp``,
on the mesh and as a served Range on the columnar ``hopbatch.delta.cdlp``
route, each against the plain numpy reference of the benchmark
(``benchmark/algorithms/cdlp.py``), and that reference against a
per-vertex walk of the specification's equations."""

from collections import Counter

import numpy as np
import pytest

from benchmark import gen, reference
from benchmark.algorithms import cdlp as ref_cdlp
from raphtory_tpu.algorithms import CDLP, LabelPropagation
from raphtory_tpu.core import events as ev
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp
from raphtory_tpu.jobs import registry
from raphtory_tpu.jobs.manager import AnalysisManager, RangeQuery

ALG = {"iterations": 10}
LIMITS = dict.fromkeys(ref_cdlp.COMPARED, 0)
N_IDS, T_SPAN = 48, 100


def _columns(seed, n_events=700):
    """Plain event columns ``(t, kind, s, d)`` in gen.py's codes: edge
    adds and deletes, vertex deletes and re-adds (revivals), self-loops,
    pairs joined both ways, and vertex 47, added once and never joined."""
    rng = np.random.default_rng(seed)
    k = rng.choice(4, n_events, p=[0.08, 0.05, 0.72, 0.15]).astype(np.uint8)
    t = np.sort(rng.integers(0, T_SPAN, n_events)).astype(np.int64)
    s = rng.integers(0, N_IDS - 1, n_events).astype(np.int64)
    d = rng.integers(0, N_IDS - 1, n_events).astype(np.int64)
    edge = k >= gen.EADD
    loops = edge & (rng.random(n_events) < 0.05)
    d[loops] = s[loops]                              # self-loops
    back = np.flatnonzero(edge)[::7]                 # (b, a) after (a, b)
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


def _walk(vm, src, dst, rounds):
    """The specification's equations, one vertex at a time."""
    lab = {int(v): int(v) for v in np.flatnonzero(vm)}
    for _ in range(rounds):
        new = {}
        for v in lab:
            hist = Counter()
            for a, b in zip(src.tolist(), dst.tolist()):
                if b == v:
                    hist[lab[a]] += 1          # a is an in-neighbour
                if a == v:
                    hist[lab[b]] += 1          # b is an out-neighbour
            best = max(hist.values(), default=0)
            new[v] = min((l for l, c in hist.items() if c == best),
                         default=lab[v])
        lab = new
    return lab


VIEWS = [(95, None), (95, 30), (60, 12)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_equals_a_per_vertex_walk_of_the_equations(seed):
    r = _ref(_columns(seed))
    saw_loop = saw_both = saw_alone = False
    for T, w in VIEWS:
        vm, src, dst = r.fold(T, w)
        lab = ref_cdlp.cdlp(vm, src, dst, 10)
        want = _walk(vm, src, dst, 10)
        assert {v: int(lab[v]) for v in want} == want
        pairs = set(zip(src.tolist(), dst.tolist()))
        saw_loop |= any(a == b for a, b in pairs)
        saw_both |= any((b, a) in pairs for a, b in pairs if a != b)
        joined = set(src.tolist()) | set(dst.tolist())
        saw_alone |= any(v not in joined for v in np.flatnonzero(vm))
    assert saw_loop and saw_both and saw_alone


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_cdlp_on_bsp_equals_the_reference(seed):
    cols = _columns(seed)
    log, r = _log(cols), _ref(cols)
    prog = CDLP(max_steps=10)
    for T, w in VIEWS:
        view = build_view(log, T)
        res, steps = bsp.run(prog, view, window=w)
        row = {"steps": int(steps),
               "result": prog.reduce(np.asarray(res), view, window=w)}
        cmp_ = ref_cdlp.compare(row, ref_cdlp.reference(*r.fold(T, w), ALG),
                                LIMITS, ALG)
        assert cmp_["ok"], (T, w, cmp_)


def _serve(log, program, q):
    from raphtory_tpu.obs.trace import TRACER

    was = TRACER.enabled
    TRACER.enable()
    try:
        job = AnalysisManager(TemporalGraph(log)).submit(program, q)
        assert job.wait(300) and job.status == "done", job.error
        spans = [e for e in TRACER.for_trace(job.trace_id) if e["ph"] == "X"]
    finally:
        (TRACER.enable if was else TRACER.disable)()
    return job, spans


@pytest.mark.parametrize("seed,windows", [(0, (30, 12)), (1, (100, 30)),
                                          (2, None)])
def test_served_range_rides_the_columnar_route_and_equals_the_reference(
        seed, windows):
    cols = _columns(seed)
    log, r = _log(cols), _ref(cols)
    q = RangeQuery(start=60, end=90, jump=30, windows=windows)
    job, spans = _serve(log, registry.resolve("CDLP", {"max_steps": 10}), q)
    names = {s["name"] for s in spans}
    assert "hop.compute" in names and "sweep.columnar" in names
    assert not names & {"bsp.dispatch", "snapshot.fold"}
    (compute,) = [s for s in spans if s["name"] == "hop.compute"]
    assert compute["args"]["kind"] == "cdlp"      # one dispatch a request
    (build,) = [s for s in spans if s["name"] == "engine.build"]
    assert build["args"]["engine"] == "HopBatchedCDLP"
    led = job.ledger.as_dict()
    assert [k for k in led["device"]["kernels"]] == ["hopbatch.delta.cdlp"]
    n_w = len(windows or (None,))
    m_pad = build["args"]["m_pad"]
    assert led["device"]["mode_rows"] == 2 * m_pad * (2 * n_w) * 10
    rows = job.results_snapshot()
    assert len(rows) == 2 * n_w
    for row in rows:
        assert row["steps"] == 10
        want = ref_cdlp.reference(*r.fold(row["time"], row["windowsize"]),
                                  ALG)
        cmp_ = ref_cdlp.compare(row, want, LIMITS, ALG)
        assert cmp_["ok"], (row["time"], row["windowsize"], cmp_)


def test_label_propagation_is_served_as_before():
    """In-neighbours only, halting at quiescence, on ``bsp``: its reduce
    is not shell safe, so no resident or columnar engine takes it."""
    job, spans = _serve(_log(_columns(0)), LabelPropagation(max_steps=30),
                        RangeQuery(start=60, end=90, jump=30, windows=(30,)))
    names = {s["name"] for s in spans}
    assert "bsp.dispatch" in names and "hop.compute" not in names
    assert all(set(row["result"]) == {"vertices", "communities", "biggest",
                                      "top5"}
               for row in job.results_snapshot())


def test_in_neighbours_control_fails_the_comparison():
    failed = 0
    for seed in (0, 1, 2):
        r = _ref(_columns(seed))
        for T, w in VIEWS:
            vm, src, dst = r.fold(T, w)
            want = ref_cdlp.reference(vm, src, dst, ALG)
            assert ref_cdlp.compare(ref_cdlp.stated(vm, src, dst, ALG),
                                    want, LIMITS, ALG)["ok"]
            failed += not ref_cdlp.compare(
                ref_cdlp.control(vm, src, dst, ALG), want, LIMITS, ALG)["ok"]
    # tiny views can agree by accident (one community either way); the
    # cell's own size is held to "every row" by benchmark/control.py
    assert failed >= 6


@pytest.mark.parametrize("comm", ["halo", "all_gather"])
def test_custom_both_is_one_histogram_on_bsp_and_on_the_mesh(comm):
    import jax

    from raphtory_tpu.parallel import sharded

    cols = _columns(3)
    view = build_view(_log(cols), 60)
    prog = CDLP(max_steps=10)
    want, steps = bsp.run(prog, view, window=12)
    vm, src, dst = _ref(cols).fold(60, 12)
    lab = ref_cdlp.cdlp(vm, src, dst, 10)
    alive = view.window_masks([12])[0][0]
    np.testing.assert_array_equal(view.vids[np.asarray(want)[alive]],
                                  lab[view.vids[alive]])
    # two aggregates merged elementwise would be another answer: the
    # in-neighbours histogram alone already differs
    lp, _ = bsp.run(LabelPropagation(max_steps=10), view, window=12)
    assert not np.array_equal(np.asarray(lp), np.asarray(want))
    mesh = sharded.make_mesh(8, 1, devices=jax.devices()[:8])
    got, msteps = sharded.run(prog, view, mesh, comm=comm, window=12)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(steps) == int(msteps) == 10


def test_label_checksum_takes_ids_of_any_width():
    from raphtory_tpu.algorithms.lpa import _label_checksum

    rng = np.random.default_rng(0)
    for hi in (1 << 20, 1 << 31, 1 << 62):
        v = rng.integers(0, hi, 500)
        l = rng.integers(0, hi, 500)
        want = sum(int(a) * int(b) % ref_cdlp.P61
                   for a, b in zip(v, l)) % ref_cdlp.P61
        assert _label_checksum(v, l) == want
    assert _label_checksum(np.zeros(0, np.int64), np.zeros(0, np.int64)) == 0


def test_segment_mode_takes_counts_and_keeps_masked_rows_in_their_segment():
    import jax
    import jax.numpy as jnp

    from raphtory_tpu.ops.segment import segment_counts, segment_mode

    rng = np.random.default_rng(5)
    m, n = 400, 37
    vals = rng.integers(0, 9, m).astype(np.int32)
    segs = rng.integers(0, n - 2, m).astype(np.int32)   # two empty segments
    mask = rng.random(m) < 0.6
    mask[segs == 3] = False                     # a segment wholly masked
    counts = segment_counts(jnp.asarray(segs), n)
    assert int(counts.sum()) == m and int(counts[n - 1]) == 0
    plain = segment_mode(jnp.asarray(vals), jnp.asarray(segs), n,
                         jnp.asarray(mask))
    given = jax.jit(lambda v, s, k, c: segment_mode(v, s, n, k, counts=c))(
        jnp.asarray(vals), jnp.asarray(segs), jnp.asarray(mask), counts)
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(given))
    for s in range(n):
        rows = vals[(segs == s) & mask]
        want = -1 if len(rows) == 0 else int(np.argmax(np.bincount(rows)))
        assert int(plain[s]) == want, s
    assert int(plain[3]) == -1 and plain.dtype == jnp.int32


@pytest.mark.parametrize("chunks", [1, 2])
def test_columns_equal_bsp_per_hop_and_window(chunks):
    from raphtory_tpu.engine.hopbatch import HopBatchedCDLP

    log = _log(_columns(4))
    hops, windows = [40, 60, 80, 95], [None, 30, 12]
    hb = HopBatchedCDLP(log, max_steps=10)
    out, steps = hb.run(hops, windows, chunks=chunks)
    out = np.asarray(out)
    assert int(steps) == 10 and out.shape[0] == len(hops) * len(windows)
    with pytest.raises(ValueError, match="warm-start"):
        HopBatchedCDLP(log).run(hops, windows, chunks=2, warm_start=True)
    for j, T in enumerate(hops):
        view = build_view(log, T)
        for i, w in enumerate(windows):
            want, _ = bsp.run(CDLP(max_steps=10), view, window=w)
            alive = (np.asarray(view.v_mask) if w is None
                     else view.window_masks([w])[0][0])
            col = out[j * len(windows) + i]
            pos = np.searchsorted(hb.tables.uv, view.vids[alive])
            np.testing.assert_array_equal(
                hb.tables.uv[col[pos]],
                view.vids[np.asarray(want)[alive]])
