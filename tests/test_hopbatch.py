"""Hop-batched columnar PageRank vs the per-view bsp path, column by
column — including logs with deletes and revivals (the hop columns carry
full fold state, not an add-only shortcut)."""

import numpy as np
import pytest

from raphtory_tpu.algorithms import PageRank
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp
from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

from test_sweep import random_log


def _assert_columns_match_per_view(hb, cols, program, log, hops, windows,
                                   agree):
    """Every column of a columnar result against ``bsp.run`` over that
    hop's ``build_view`` — this file's per-view oracle, all of a view's
    windowed vertices at once. ``agree(want, got, view, uv)``
    compares the two value vectors over those vertices."""
    cols = np.asarray(cols)
    assert cols.shape == (len(hops) * len(windows), hb.tables.n_pad)
    for j, T in enumerate(hops):
        view = build_view(log, T)
        want, _ = bsp.run(program, view,
                          windows=[-1 if w is None else w for w in windows])
        want = np.asarray(want)
        pos = np.searchsorted(hb.tables.uv, view.vids)
        for i, w in enumerate(windows):
            mask = (np.asarray(view.v_mask) if w is None
                    else view.window_masks([w])[0][0])
            agree(want[i][mask], cols[j * len(windows) + i][pos[mask]],
                  view, hb.tables.uv)


def _close(atol):
    # columns against bsp: two different sums of the same ranks (a scan
    # over [C, m] rows against a scan over the view's own flat rows), so
    # the ranks agree to float32 reassociation, not bit for bit
    def agree(want, got, view, uv):
        np.testing.assert_allclose(got, want, atol=atol, rtol=0)
    return agree


def _same(want, got, view, uv):
    np.testing.assert_array_equal(want, got)     # inf == inf


def _same_component(want, got, view, uv):
    # both label spaces decode to the component's min vid
    np.testing.assert_array_equal(view.vids[want], uv[got])


@pytest.mark.parametrize("seed", [0, 5])
def test_hopbatch_matches_per_view_pagerank(seed):
    rng = np.random.default_rng(seed)
    log = random_log(rng, n_events=600, n_ids=40, t_span=80)
    hops = [20, 45, 46, 79]
    windows = [100, 30, None]
    hb = HopBatchedPageRank(log, tol=1e-7, max_steps=20)
    ranks, steps = hb.run(hops, windows)
    _assert_columns_match_per_view(hb, ranks, PageRank(max_steps=20,
                                                       tol=1e-7),
                                   log, hops, windows, _close(2e-5))


def test_hopbatch_rejects_unsorted_hops_and_is_reusable():
    log = random_log(np.random.default_rng(2), n_events=200, n_ids=20,
                     t_span=50)
    hb = HopBatchedPageRank(log, max_steps=10)
    with pytest.raises(ValueError):
        hb.run([30, 10], [None])
    r1, _ = hb.run([10, 30], [50])
    # a batch starting BEFORE the advanced fold clock must refuse — it
    # would silently compute from the later fold state
    with pytest.raises(ValueError, match="forward"):
        hb.run([5], [50])
    # a second batch continuing FORWARD reuses the same host fold
    r2, _ = hb.run([40, 49], [50])
    assert np.asarray(r2).shape == np.asarray(r1).shape
    # sanity: ranks are a distribution per column over the masked set
    s = np.asarray(r2).sum(axis=1)
    assert np.all((s > 0.99) & (s < 1.01))


@pytest.mark.parametrize("seed", [1, 9])
def test_hopbatch_cc_matches_per_view(seed):
    from raphtory_tpu.algorithms import ConnectedComponents
    from raphtory_tpu.engine.hopbatch import HopBatchedCC

    rng = np.random.default_rng(seed)
    log = random_log(rng, n_events=500, n_ids=35, t_span=70)
    hops = [25, 69]
    windows = [100, 20]
    hb = HopBatchedCC(log, max_steps=60)
    labels, _ = hb.run(hops, windows)
    _assert_columns_match_per_view(hb, labels,
                                   ConnectedComponents(max_steps=60),
                                   log, hops, windows, _same_component)


@pytest.mark.parametrize("directed", [False, True])
def test_hopbatch_bfs_matches_per_view(directed):
    from raphtory_tpu.algorithms import SSSP
    from raphtory_tpu.engine.hopbatch import HopBatchedBFS

    rng = np.random.default_rng(6)
    log = random_log(rng, n_events=400, n_ids=30, t_span=60)
    hops = [25, 59]
    windows = [100, 15]
    seeds = (0, 1, 2)
    hb = HopBatchedBFS(log, seeds, directed=directed, max_steps=40)
    dist, _ = hb.run(hops, windows)
    bfs = SSSP(seeds=seeds, weight_prop=None, directed=directed,
               max_steps=40)
    _assert_columns_match_per_view(hb, dist, bfs, log, hops, windows,
                                   _same)


@pytest.mark.parametrize("chunks", [2, 3, 6])
def test_hopbatch_chunked_matches_one_dispatch(chunks):
    """The pipelined chunked sweep must match chunks=1 for all three
    engines (hop-major concatenation over 6 hops, so every parametrized
    chunk count genuinely splits the sweep). PageRank compares at a hair
    under the solver tolerance, not bitwise: the chunked sweep compiles an
    H=len/chunks program whose segment-sum fusion can round differently
    from the H=6 one on some XLA versions (~1e-8 observed on XLA 0.4
    CPU). CC/BFS are integer/min-plus — exact on every backend."""
    from raphtory_tpu.engine.hopbatch import HopBatchedBFS, HopBatchedCC

    rng = np.random.default_rng(11)
    log = random_log(rng, n_events=800, n_ids=50, t_span=100)
    hops = [20, 40, 60, 80, 85, 99]
    windows = [1000, 25]
    one = np.asarray(
        HopBatchedPageRank(log, tol=1e-7, max_steps=20).run(hops, windows)[0])
    many = np.asarray(HopBatchedPageRank(log, tol=1e-7, max_steps=20)
                      .run(hops, windows, chunks=chunks)[0])
    np.testing.assert_allclose(one, many, rtol=1e-5, atol=1e-7)

    one_cc = np.asarray(HopBatchedCC(log, max_steps=60).run(hops, windows)[0])
    many_cc = np.asarray(HopBatchedCC(log, max_steps=60)
                         .run(hops, windows, chunks=chunks)[0])
    np.testing.assert_array_equal(one_cc, many_cc)

    seeds = (0, 1, 2)
    one_b = np.asarray(HopBatchedBFS(log, seeds, directed=False, max_steps=40)
                       .run(hops, windows)[0])
    many_b = np.asarray(HopBatchedBFS(log, seeds, directed=False, max_steps=40)
                        .run(hops, windows, chunks=chunks)[0])
    np.testing.assert_array_equal(one_b, many_b)


def test_hopbatch_uneven_chunks_fall_back():
    """A chunk count that doesn't divide the sweep still returns correct
    (one-dispatch) results rather than erroring."""
    rng = np.random.default_rng(12)
    log = random_log(rng, n_events=400, n_ids=30, t_span=60)
    hops = [20, 40, 59]
    one = np.asarray(
        HopBatchedPageRank(log, tol=1e-7, max_steps=15).run(hops, [100])[0])
    two = np.asarray(HopBatchedPageRank(log, tol=1e-7, max_steps=15)
                     .run(hops, [100], chunks=2)[0])
    np.testing.assert_array_equal(one, two)


def test_hopbatch_warm_start_matches_cold_within_tol():
    """Warm-started chunked sweeps converge to the same fixed point as the
    cold one-dispatch sweep (agreement to solver tolerance, not bitwise),
    and non-contraction engines refuse the flag."""
    from raphtory_tpu.engine.hopbatch import HopBatchedCC

    rng = np.random.default_rng(21)
    log = random_log(rng, n_events=900, n_ids=60, t_span=120)
    hops = [30, 60, 90, 100, 110, 119]
    windows = [1000, 40]
    cold = np.asarray(HopBatchedPageRank(log, tol=1e-9, max_steps=100)
                      .run(hops, windows)[0])
    warm = np.asarray(HopBatchedPageRank(log, tol=1e-9, max_steps=100)
                      .run(hops, windows, chunks=3, warm_start=True)[0])
    np.testing.assert_allclose(cold, warm, atol=1e-6, rtol=0)

    with pytest.raises(ValueError, match="warm-start"):
        HopBatchedCC(log).run(hops, windows, chunks=3, warm_start=True)


def test_hopbatch_weighted_sssp_matches_per_view():
    from raphtory_tpu.algorithms import SSSP
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.engine.hopbatch import HopBatchedSSSP

    rng = np.random.default_rng(8)
    n = 700
    src = rng.integers(0, 40, n)
    dst = rng.integers(0, 40, n)
    times = np.sort(rng.integers(0, 90, n))   # ties exercise the
    log = EventLog()                          # (time, row) tie-break
    log.append_batch(
        times, np.full(n, 2, np.uint8), src.astype(np.int64),
        dst.astype(np.int64),
        props=[(i, {"weight": float(rng.uniform(0.5, 3.0))})
               for i in range(n)])
    hops = [30, 60, 89]
    windows = [1000, 25]
    seeds = (0, 1, 2)
    hb = HopBatchedSSSP(log, seeds, "weight", directed=False, max_steps=60)
    dist, _ = hb.run(hops, windows)
    dist = np.asarray(dist)

    prog = SSSP(seeds=seeds, weight_prop="weight", directed=False,
                max_steps=60)
    for j, T in enumerate(hops):
        view = build_view(log, T)
        want, _ = bsp.run(prog, view, windows=windows)
        for i, w in enumerate(windows):
            col = dist[j * len(windows) + i]
            mask = view.window_masks([w])[0][0]
            for vi, vid in enumerate(view.vids):
                if not mask[vi]:
                    continue
                p = int(np.searchsorted(hb.tables.uv, vid))
                a = float(np.asarray(want)[i, vi])
                b = float(col[p])
                assert (np.isinf(a) and np.isinf(b)) or \
                    a == pytest.approx(b, abs=1e-5), (T, w, int(vid), a, b)


def test_hopbatch_weighted_sssp_rejects_immutable_key():
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.engine.hopbatch import HopBatchedSSSP

    log = EventLog()
    log.append_batch(np.array([1, 2]), np.full(2, 2, np.uint8),
                     np.array([0, 1]), np.array([1, 2]),
                     props=[(0, {"!weight": 2.0}), (1, {"!weight": 3.0})])
    with pytest.raises(ValueError, match="immutable"):
        HopBatchedSSSP(log, (0,), "weight")


def test_hopbatch_weighted_sssp_treats_stored_nan_as_unit():
    """An explicitly-stored NaN weight must weigh 1.0 (SSSP.message's
    rule), not poison the min-plus relaxation."""
    from raphtory_tpu.algorithms import SSSP
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.engine.hopbatch import HopBatchedSSSP

    log = EventLog()
    log.append_batch(np.array([1, 2]), np.full(2, 2, np.uint8),
                     np.array([0, 1]), np.array([1, 2]),
                     props=[(0, {"weight": float("nan")}),
                            (1, {"weight": 2.0})])
    hb = HopBatchedSSSP(log, (0,), "weight", directed=True, max_steps=10)
    dist = np.asarray(hb.run([5], [1000])[0])[0]
    view = build_view(log, 5)
    want, _ = bsp.run(SSSP(seeds=(0,), weight_prop="weight", directed=True,
                           max_steps=10), view, windows=[1000])
    for vi, vid in enumerate(view.vids[: view.n_active]):
        p = int(np.searchsorted(hb.tables.uv, vid))
        assert float(np.asarray(want)[0, vi]) == float(dist[p]), int(vid)


def test_hopbatch_weighted_sssp_chunked_matches_one_dispatch():
    """The weight-fold cursor must continue correctly across pipelined
    chunks (the LDBC bench runs weighted SSSP with chunks=5)."""
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.engine.hopbatch import HopBatchedSSSP

    rng = np.random.default_rng(14)
    n = 800
    src = rng.integers(0, 45, n)
    dst = rng.integers(0, 45, n)
    times = np.sort(rng.integers(0, 120, n))
    log = EventLog()
    log.append_batch(
        times, np.full(n, 2, np.uint8), src.astype(np.int64),
        dst.astype(np.int64),
        props=[(i, {"weight": float(rng.uniform(0.5, 3.0))})
               for i in range(n)])
    hops = [20, 40, 60, 80, 100, 119]
    windows = [1000, 30]
    seeds = (0, 1)
    one = np.asarray(HopBatchedSSSP(log, seeds, "weight", directed=False,
                                    max_steps=60).run(hops, windows)[0])
    for chunks in (2, 3):
        many = np.asarray(
            HopBatchedSSSP(log, seeds, "weight", directed=False,
                           max_steps=60).run(hops, windows,
                                             chunks=chunks)[0])
        np.testing.assert_array_equal(one, many)


def test_delta_fold_matches_host_columns(monkeypatch):
    """The device-rebuilt masks (base + per-hop deltas) produce bitwise
    the same results as the host-built [H, m_pad] columns, deletes and
    revivals included, for PR and CC and BFS."""
    import numpy as np

    from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                              HopBatchedPageRank)

    log = random_log(np.random.default_rng(11), n_events=900, n_ids=40,
                     t_span=1000, props=True)   # deletes + weight props
    hops = [300, 500, 700, 900]
    windows = [250, None]

    from raphtory_tpu.engine.hopbatch import HopBatchedSSSP

    for cls, kw in ((HopBatchedPageRank, dict(tol=0.0, max_steps=8)),
                    (HopBatchedCC, dict(max_steps=30)),
                    (HopBatchedBFS, dict(seeds=(1, 2), max_steps=30)),
                    (HopBatchedSSSP, dict(seeds=(1, 2), max_steps=30,
                                          weight_prop="w"))):
        monkeypatch.setenv("RTPU_FOLD", "host")
        host, s1 = cls(log, **kw).run(hops, windows)
        monkeypatch.setenv("RTPU_FOLD", "delta")
        delta, s2 = cls(log, **kw).run(hops, windows)
        np.testing.assert_array_equal(np.asarray(host), np.asarray(delta))
        assert int(s1) == int(s2)


def test_delta_fold_chunked_warm_start(monkeypatch):
    import numpy as np

    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    log = random_log(np.random.default_rng(12), n_events=900, n_ids=40,
                     t_span=1000)
    hops = [200, 400, 600, 800]
    monkeypatch.setenv("RTPU_FOLD", "delta")
    one, _ = HopBatchedPageRank(log, tol=1e-9, max_steps=300).run(
        hops, [300], chunks=1)
    piped, _ = HopBatchedPageRank(log, tol=1e-9, max_steps=300).run(
        hops, [300], chunks=2, warm_start=True)
    np.testing.assert_allclose(np.asarray(one), np.asarray(piped),
                               atol=5e-7)


def test_fold_mode_toggle_keeps_delta_base_fresh(monkeypatch):
    """host-path calls on a shared engine invalidate the running delta
    base, so a later delta call rebuilds instead of scattering one hop
    onto a stale base."""
    import numpy as np

    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    log = random_log(np.random.default_rng(13), n_events=900, n_ids=40,
                     t_span=1000)
    ref_log = random_log(np.random.default_rng(13), n_events=900, n_ids=40,
                         t_span=1000)
    hb = HopBatchedPageRank(log, tol=0.0, max_steps=8)
    monkeypatch.setenv("RTPU_FOLD", "delta")
    hb.run([100, 200], [None])
    monkeypatch.setenv("RTPU_FOLD", "host")
    hb.run([300, 400], [None])
    monkeypatch.setenv("RTPU_FOLD", "delta")
    got, _ = hb.run([500, 600], [None])
    ref, _ = HopBatchedPageRank(ref_log, tol=0.0, max_steps=8).run(
        [500, 600], [None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_edge_tiled_pagerank_matches_single_shot(monkeypatch):
    """Forcing the edge-tile path (tiny payload budget) matches the
    single-shot kernel to f32 reassociation tolerance — and provably
    TOOK the tiled path (m_pad must exceed the 2^16 single-shot floor)."""
    import numpy as np

    from raphtory_tpu.engine import hopbatch as hb_mod
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    # >2^16 distinct pairs so the tile floor doesn't bypass tiling
    log = random_log(np.random.default_rng(21), n_events=180_000,
                     n_ids=2_000, t_span=5_000, props=True)
    hops = [2_000, 3_500, 5_000]
    windows = [2_500, None]
    hb1 = HopBatchedPageRank(log, tol=0.0, max_steps=8)
    assert hb1.tables.m_pad > (1 << 16)
    one, s1 = hb1.run(hops, windows)
    one = np.asarray(one)

    orig = hb_mod._edge_tile_for
    used = []

    def tiny_budget(m_pad, C, budget_bytes=1 << 28):
        t = orig(m_pad, C, budget_bytes=1 << 18)
        used.append(t)
        return t

    from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                              HopBatchedSSSP)

    cc_one, _ = HopBatchedCC(log, max_steps=30).run(hops, windows)
    bfs_one, _ = HopBatchedBFS(log, (1, 2), max_steps=30).run(hops, windows)
    sssp_one, _ = HopBatchedSSSP(log, (1, 2), "w", max_steps=30).run(
        hops, windows)

    monkeypatch.setattr(hb_mod, "_edge_tile_for", tiny_budget)
    for c in (hb_mod._compiled, hb_mod._compiled_delta, hb_mod._compiled_cc,
              hb_mod._compiled_bfs):
        c.cache_clear()
    try:
        tiled, s2 = HopBatchedPageRank(log, tol=0.0, max_steps=8).run(
            hops, windows)
        assert used and used[-1] is not None   # the tiled path really ran
        # single-shot is the segmented scan, tiled the scatter a tile:
        # another order of the same float32 adds, hence a tolerance
        np.testing.assert_allclose(one, np.asarray(tiled), atol=1e-6)
        assert int(s1) == int(s2)
        # min-combine kernels tile exactly (no reassociation concern)
        cc_t, _ = HopBatchedCC(log, max_steps=30).run(hops, windows)
        np.testing.assert_array_equal(np.asarray(cc_one), np.asarray(cc_t))
        bfs_t, _ = HopBatchedBFS(log, (1, 2), max_steps=30).run(
            hops, windows)
        np.testing.assert_array_equal(np.asarray(bfs_one),
                                      np.asarray(bfs_t))
        sssp_t, _ = HopBatchedSSSP(log, (1, 2), "w", max_steps=30).run(
            hops, windows)
        np.testing.assert_array_equal(np.asarray(sssp_one),
                                      np.asarray(sssp_t))
    finally:
        for c in (hb_mod._compiled, hb_mod._compiled_delta,
                  hb_mod._compiled_cc, hb_mod._compiled_bfs):
            c.cache_clear()


def test_delta_fold_resident_across_batches(monkeypatch):
    """A second delta run() on a live engine ships NO base snapshot (the
    device-resident advanced state is the base; hop 0's catch-up rides the
    delta[0] slot) and still matches a fresh engine bitwise — CC and
    weighted SSSP, deletes/revivals/weight updates included."""
    import numpy as np

    from raphtory_tpu.engine.hopbatch import HopBatchedCC, HopBatchedSSSP

    monkeypatch.setenv("RTPU_FOLD", "delta")
    for cls, kw in ((HopBatchedCC, dict(max_steps=30)),
                    (HopBatchedSSSP, dict(seeds=(1, 2), max_steps=30,
                                          weight_prop="w"))):
        log = random_log(np.random.default_rng(21), n_events=900, n_ids=40,
                         t_span=1000, props=True)
        hb = cls(log, **kw)
        hb.run([200, 350], [250, None])
        assert hb._dev_base is not None
        # prove the second batch goes all-delta: a shipped base would be a
        # non-None payload[0]
        _, payload = hb._fold_deltas([500, 700])
        assert payload[0] is None
        got, _ = hb._dispatch_deltas(payload, [500, 700], [250, None])
        fresh, _ = cls(log, **kw).run([500, 700], [250, None])
        np.testing.assert_array_equal(np.asarray(got), np.asarray(fresh))


def test_delta_fold_residency_drops_on_dispatch_failure(monkeypatch):
    """A dispatch-time error invalidates the device-resident base, so the
    next batch falls back to shipping a fresh snapshot (no silent
    mis-sync between the host fold and a stale device state)."""
    import numpy as np
    import pytest

    from raphtory_tpu.engine import hopbatch
    from raphtory_tpu.engine.hopbatch import HopBatchedCC

    monkeypatch.setenv("RTPU_FOLD", "delta")
    log = random_log(np.random.default_rng(22), n_events=600, n_ids=30,
                     t_span=1000)
    hb = HopBatchedCC(log, max_steps=30)
    hb.run([200, 350], [None])
    assert hb._dev_base is not None

    def boom(*a, **k):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(hopbatch, "run_columns_delta", boom)
    with pytest.raises(RuntimeError, match="injected"):
        hb.run([500], [None])
    assert hb._dev_base is None
    monkeypatch.undo()
    monkeypatch.setenv("RTPU_FOLD", "delta")
    got, _ = hb.run([700, 900], [None])
    fresh, _ = HopBatchedCC(log, max_steps=30).run([700, 900], [None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fresh))


def test_device_edge_tables_cached_per_log():
    """Cold engines over the same unchanged log share ONE device upload
    of the static (src, dst) tables (the largest per-query transfer);
    the cache invalidates when the log grows."""
    import numpy as np

    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    log = random_log(np.random.default_rng(23), n_events=400, n_ids=30,
                     t_span=500)
    a = HopBatchedPageRank(log, max_steps=4)
    b = HopBatchedPageRank(log, max_steps=4)
    assert a._e_src is b._e_src and a._e_dst is b._e_dst

    log.add_edge(600, 1_000_001, 1_000_002)   # new pair -> new tables
    c = HopBatchedPageRank(log, max_steps=4)
    assert c._e_src is not a._e_src
    np.testing.assert_array_equal(np.asarray(c.tables.e_src)[: c.tables.m],
                                  np.asarray(c._e_src)[: c.tables.m])


@pytest.mark.parametrize("seed", [2, 8, 10, 24])
def test_delta_fold_residency_drops_on_fold_failure(monkeypatch, seed):
    """An exception INSIDE the fold (e.g. a hop_callback raising after
    the host base absorbed part of the batch) drops BOTH the device
    residency and the running host base: the aborted advance consumed
    events that neither captured (last_delta spans only the latest
    advance), so the next run must re-materialise from the sweep's full
    state. Seeds 2/8/10 reproduced the stale-host-base corruption when
    only the device side was cleared."""
    import numpy as np
    import pytest

    from raphtory_tpu.engine.hopbatch import HopBatchedCC

    monkeypatch.setenv("RTPU_FOLD", "delta")
    log = random_log(np.random.default_rng(seed), n_events=600, n_ids=30,
                     t_span=1000)
    hb = HopBatchedCC(log, max_steps=30)
    hb.run([200, 350], [None])
    assert hb._dev_base is not None

    def cb(T, sw):
        if T >= 500:
            raise RuntimeError("injected fold failure")

    with pytest.raises(RuntimeError, match="injected"):
        hb.run([500, 650], [None], hop_callback=cb)
    assert hb._dev_base is None
    got, _ = hb.run([700, 900], [None])
    fresh, _ = HopBatchedCC(log, max_steps=30).run([700, 900], [None])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(fresh))


def test_ship_bytes_accounting(monkeypatch):
    """ship_bytes reflects the resident-base design at realistic shapes
    (hops covering a narrow late slice of a larger log, like the GAB
    bench): the delta sweep ships base once + small pads vs the host
    path's H full folds, and a follow-on batch on the live engine ships
    no base at all."""
    import numpy as np

    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    # 2000 ids keeps per-vertex degree (and so delete killList fan-out,
    # which legitimately inflates per-hop touched-pair deltas) moderate
    rng = np.random.default_rng(31)
    log = random_log(rng, n_events=40_000, n_ids=2_000, t_span=10_000)
    hops = [8_500, 8_600, 8_700, 8_800]

    monkeypatch.setenv("RTPU_FOLD", "host")
    hb_host = HopBatchedPageRank(log, max_steps=4)
    hb_host.run(hops, [3_000])
    t = hb_host.tables
    per_row = np.dtype(t.tdtype).itemsize + 1
    base_bytes = (t.m_pad + t.n_pad) * per_row
    assert hb_host.ship_bytes >= len(hops) * base_bytes

    monkeypatch.setenv("RTPU_FOLD", "delta")
    hb = HopBatchedPageRank(log, max_steps=4)
    hb.run(hops, [3_000], chunks=2, warm_start=True)
    # base ships once (chunk 1 only) + per-hop pads — under the H folds
    # the host path ships
    run1 = hb.ship_bytes
    assert 0 < run1 < hb_host.ship_bytes
    # a follow-on batch on the live engine is all-delta: no base at all,
    # so it ships less than one base snapshot (and less than run 1)
    hb.run([8_900, 9_000], [3_000])
    assert hb.ship_bytes < base_bytes and hb.ship_bytes < run1


# ---------------------------------------------------------------------------
# one edge table at the widths the benchmark's cells dispatch


def _weighted_delete_log():
    from raphtory_tpu.core.events import EventLog

    rng = np.random.default_rng(4)
    log = EventLog()
    for _ in range(400):
        s, d = int(rng.integers(0, 25)), int(rng.integers(0, 25))
        log.add_edge(int(rng.integers(0, 60)), s, d,
                     {"w": float(rng.uniform(0.5, 3.0))})
        if rng.random() < 0.15:
            log.delete_edge(int(rng.integers(0, 60)), s, d)
    return log


def _family(name):
    """(logs, engine of a log, per-view program, agree) of one columnar
    family, on adversarial delete/tombstone logs."""
    from raphtory_tpu.algorithms import SSSP, ConnectedComponents
    from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                              HopBatchedSSSP)

    def logs(seeds, **kw):
        return [random_log(np.random.default_rng(s), **kw) for s in seeds]

    if name == "pagerank":
        return (logs((0, 7), n_events=600, n_ids=40, t_span=80),
                lambda log: HopBatchedPageRank(log, tol=1e-7, max_steps=20),
                PageRank(max_steps=20, tol=1e-7), _close(2e-6))
    if name == "cc":
        return (logs((1, 9), n_events=500, n_ids=35, t_span=70),
                lambda log: HopBatchedCC(log, max_steps=60),
                ConnectedComponents(max_steps=60), _same_component)
    if name.startswith("bfs"):
        directed = name == "bfs-directed"
        return (logs((6,), n_events=400, n_ids=30, t_span=60),
                lambda log: HopBatchedBFS(log, (0, 1, 2), directed=directed,
                                          max_steps=40),
                SSSP(seeds=(0, 1, 2), weight_prop=None, directed=directed,
                     max_steps=40), _same)
    return ([_weighted_delete_log()],
            lambda log: HopBatchedSSSP(log, (0, 1), "w", directed=False,
                                       max_steps=40),
            SSSP(seeds=(0, 1), weight_prop="w", directed=False,
                 max_steps=40), _same)


#: C columns as (hops, windows): 1 = the Live and View dispatch, 3 = a mesh
#: chip's share of a Range, 6 = the one-chip Range chunk, 12 = the whole
#: Range in one dispatch (ROADMAP S1 (ii))
CELL_WIDTHS = {1: ([59], [30]), 3: ([59], [100, 30, None]),
               6: ([25, 59], [100, 30, None]),
               12: ([20, 45, 46, 59], [100, 30, None])}


@pytest.mark.parametrize("C", sorted(CELL_WIDTHS))
@pytest.mark.parametrize("family", ["pagerank", "cc", "bfs-directed",
                                    "bfs-undirected", "sssp-weighted"])
def test_columnar_matches_per_view_at_cell_widths(family, C):
    """The columnar kernel over the dst-sorted pair table agrees with the
    per-view engine at every column count a cell dispatches: PageRank to
    reduction-order tolerance, the min-merge families bitwise."""
    hops, windows = CELL_WIDTHS[C]
    logs, engine, program, agree = _family(family)
    for log in logs:
        hb = engine(log)
        out, _ = hb.run(hops, windows)
        _assert_columns_match_per_view(hb, out, program, log, hops,
                                       windows, agree)


@pytest.fixture(scope="module")
def wide_log():
    """> 2^16 distinct pairs: past the floor under which nothing tiles."""
    from raphtory_tpu.utils.synth import gab_like_log

    return gab_like_log(n_vertices=6000, n_edges=150_000, t_span=1000)


@pytest.mark.parametrize("C", [1, 12])
@pytest.mark.parametrize("family", ["pagerank", "cc"])
def test_edge_tiled_matches_single_shot_at_cell_widths(monkeypatch,
                                                       wide_log, family, C):
    """The ``lax.scan`` over edge tiles PLUS a remainder slice (a tile
    that does not divide ``m_pad``) against the single-shot kernel, at the
    narrowest and the widest dispatch: PageRank to f32 reassociation
    tolerance, min-label propagation exactly."""
    from raphtory_tpu.engine import hopbatch as hb_mod
    from raphtory_tpu.engine.hopbatch import HopBatchedCC

    hops, windows = {1: ([900], [200]),
                     12: ([400, 600, 800, 999], [1000, 200, None])}[C]
    engine = {"pagerank": lambda: HopBatchedPageRank(wide_log, tol=0.0,
                                                     max_steps=6),
              "cc": lambda: HopBatchedCC(wide_log, max_steps=30)}[family]
    hb = engine()
    one, s1 = hb.run(hops, windows)
    m_pad, tiles = hb.tables.m_pad, []

    def odd_tile(m_pad, C, budget_bytes):
        tiles.append((m_pad, 40_000))
        return 40_000

    monkeypatch.setattr(hb_mod, "_edge_tile_for", odd_tile)
    hb_mod._compiled_delta.cache_clear()    # the tile is chosen at trace time
    try:
        tiled, s2 = engine().run(hops, windows)
    finally:
        hb_mod._compiled_delta.cache_clear()
    assert tiles and all(m == m_pad and m > t and m % t
                         for m, t in tiles)
    assert int(s1) == int(s2)
    if family == "pagerank":
        # scan (single-shot) against scatter (tiled): the sum reassociates
        np.testing.assert_allclose(np.asarray(one), np.asarray(tiled),
                                   atol=1e-6, rtol=0)
    else:
        np.testing.assert_array_equal(np.asarray(one), np.asarray(tiled))


def test_logs_padding_to_one_shape_share_one_compiled_program():
    """No property of a log but its padded shapes is in a program's cache
    key: two engines over logs whose pair counts differ, padded to the
    same ``m_pad`` / ``n_pad``, dispatch ONE ``_compiled_delta`` entry
    (PERF.md section 6, PR 29: every Live rebase used to build one)."""
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.engine import hopbatch as hb_mod

    def ring(n_pairs):
        log = EventLog()
        for i in range(n_pairs):
            log.add_edge(i % 50, i % 30, (i * 7 + 1 + i // 30) % 30)
        return log

    hops, windows = [20, 49], [100, 10]
    a = HopBatchedPageRank(ring(200), tol=0.0, max_steps=3)
    a.run(hops, windows)
    misses = hb_mod._compiled_delta.cache_info().misses
    b = HopBatchedPageRank(ring(230), tol=0.0, max_steps=3)
    assert b.tables.m != a.tables.m
    assert (b.tables.m_pad, b.tables.n_pad) == (a.tables.m_pad,
                                                a.tables.n_pad)
    b.run(hops, windows)
    assert hb_mod._compiled_delta.cache_info().misses == misses


# ---------------------------------------------------------------------------
# what a dispatch is measured by


def test_instrument_records_refined_fields(monkeypatch):
    import jax
    import jax.numpy as jnp

    from raphtory_tpu.obs import ledger as ledger_mod

    monkeypatch.setenv("RTPU_LEDGER", "1")
    traffic = ledger_mod.edge_traffic_model(3_735_552, 12, 131_072)
    # every pair gathers a row and read-modify-writes one, each a whole
    # 64 B line here (the state outgrows the modelled cache)
    assert traffic["random_rows"] == 2 * 3_735_552
    assert traffic["est_hbm_bytes"] \
        == 3 * 3_735_552 * 64 + traffic["streamed_bytes"]
    # a state that fits the cache costs its payload bytes, not lines
    tiny = ledger_mod.edge_traffic_model(4096, 4, 256)
    assert tiny["est_hbm_bytes"] == 4096 * (2 * 4 + 4) + 3 * 4096 * 16
    fn = ledger_mod.instrument("test.edge_traffic",
                               jax.jit(lambda x: x * 2.0), traffic=traffic)
    out = fn(jnp.arange(8, dtype=jnp.float32))
    jax.block_until_ready(out)
    rec = [r for r in ledger_mod.REGISTRY.snapshot()
           if r["kernel"] == "test.edge_traffic"][0]
    assert rec["est_hbm_bytes"] == traffic["est_hbm_bytes"]
    assert rec["traffic_model"]["model"] == "edge_superstep"
    if rec["mode"] == "xla":                   # harvest available
        assert rec["bound_refined"] in ("hbm_bound", "compute_bound")
        # the raw XLA harvest stays untouched next to the model
        assert rec["bytes_accessed"] != rec["est_hbm_bytes"]
    # /costz surfaces both classifications
    cz = ledger_mod.costz()
    assert "kernels_by_bound_refined" in cz
    assert "est_hbm_bytes" in str(cz["classification_rule"])


@pytest.mark.parametrize("size", ["past_2^17_pairs", "small"])
def test_columnar_run_dispatches_on_the_sorted_table(monkeypatch, size,
                                                     request):
    """A columnar run hands its kernel the ``tables.m_pad`` rows of the
    dst-sorted pair table and nothing after the documented operands, at
    every size, and wraps each dispatch's host preparation in an
    ``engine.layout stage=payload`` span (the span the benchmark's
    ``range.layout_share`` reads)."""
    from raphtory_tpu.engine import hopbatch as hb_mod
    from raphtory_tpu.obs.trace import TRACER

    if size == "small":
        log = random_log(np.random.default_rng(5), n_events=600, n_ids=40,
                         t_span=80)
        hops, windows = [20, 45, 46, 79], [100, 30, None]
    else:
        log = request.getfixturevalue("wide_log")
        hops, windows = [700, 900], [1000, 200]
    hb = HopBatchedPageRank(log, tol=0, max_steps=3)
    assert (hb.tables.m_pad >= 1 << 17) == (size != "small")
    real, seen = hb_mod._compiled_delta, []

    def spy(*key):
        runner = real(*key)

        def run(*operands):
            seen.append((key[2], [tuple(o.shape) for o in operands]))
            return runner(*operands)
        return run

    monkeypatch.setattr(hb_mod, "_compiled_delta", spy)
    was = TRACER.enabled
    TRACER.enable()
    try:
        before = TRACER.recorded
        out, _ = hb.run(hops, windows)
        events = TRACER.recent(TRACER.recorded - before)
    finally:
        (TRACER.enable if was else TRACER.disable)()
    assert np.asarray(out).shape == (len(hops) * len(windows),
                                     hb.tables.n_pad)
    spans = [e["args"] for e in events if e["name"] == "engine.layout"]
    assert len(spans) == len(seen) == 1
    assert spans[0]["stage"] == "payload" and spans[0]["cached"] is False
    m_pad, shapes = seen[0]
    assert m_pad == hb.tables.m_pad
    # e_src, e_dst, pair base (lat, alive): the pair table's rows; then
    # the vertex base, the two delta triples and the column descriptors
    assert shapes[:4] == [(hb.tables.m_pad,)] * 4 and len(shapes) == 14


def test_one_chip_default_route_is_the_mesh_routes_table():
    """One table on one chip and on the mesh: the columnar run agrees
    with ``run_columns_sharded`` over the same fold columns to the
    tolerance tests/test_columns_sharded.py holds the mesh to."""
    import jax

    from raphtory_tpu.parallel.columns import run_columns_sharded

    log = random_log(np.random.default_rng(3), n_events=900, n_ids=50,
                     t_span=100)
    hops, windows = [20, 40, 60, 80, 99], [1000, 25]
    one, steps1 = HopBatchedPageRank(log, tol=1e-7, max_steps=20).run(
        hops, windows)
    hb = HopBatchedPageRank(log, tol=1e-7, max_steps=20)
    _, cols = hb._fold_columns([int(x) for x in hops])
    many, steps2 = run_columns_sharded(
        hb.tables, *cols, hops, windows, jax.devices()[:4],
        tol=1e-7, max_steps=20)
    np.testing.assert_allclose(np.asarray(one), np.asarray(many),
                               rtol=1e-5, atol=1e-7)
    assert int(steps1) == int(steps2)


@pytest.mark.parametrize("pack,hops,windows,tile", [
    (4, [40, 70, 99], [1000, 25], None), (16, [40, 70, 99], [1000, 25], None),
    (128, [99], [25], None), (128, [99], [25], 300)],
    ids=["P4", "P16", "one-column", "one-column-tiled"])
def test_packed_gather_table_is_a_selection_bit_for_bit(monkeypatch, pack,
                                                        hops, windows, tile):
    """Past ``TABLE_BYTES`` the superstep's gather table holds P vertices
    a row (so that it stays in the chip's fast memory), a table of one
    column always does (its plain form is the flat gather), and a pair
    keeps its slot of the row it read (all the ids at once, or ``tile`` of
    them at a time): the same floats, so (on the CPU
    backend, where no reduction's order depends on a layout) the same ranks
    bit for bit, one route with itself — and the dispatch really packed."""
    from raphtory_tpu.engine import hopbatch as hb_mod
    from raphtory_tpu.obs.trace import TRACER
    from raphtory_tpu.ops import gather as gather_ops

    log = random_log(np.random.default_rng(33), n_events=2000, n_ids=300,
                     t_span=100)
    C = len(hops) * len(windows)

    def run():
        hb_mod._compiled_delta.cache_clear()    # the pack is chosen at trace time
        was = TRACER.enabled
        TRACER.enable()
        try:
            before = TRACER.recorded
            out, steps = HopBatchedPageRank(log, tol=0.0, max_steps=20).run(
                hops, windows)
            events = TRACER.recent(TRACER.recorded - before)
        finally:
            (TRACER.enable if was else TRACER.disable)()
            hb_mod._compiled_delta.cache_clear()
        (span,) = [e["args"] for e in events if e["name"] == "hop.compute"]
        return np.asarray(out), int(steps), span["gather_pack"]

    n_pad = HopBatchedPageRank(log).tables.n_pad
    monkeypatch.setattr(gather_ops, "ONE_COLUMN_PACK", 1)
    assert hb_mod._gather_pack(n_pad, C) == 1
    one, s1, p1 = run()
    if C == 1:
        monkeypatch.undo()    # the rule as it stands: as many as divide n
        if tile:
            monkeypatch.setattr(gather_ops, "PACKED_ROWS_BYTES", tile * 512)
    else:
        monkeypatch.setattr(gather_ops, "TABLE_BYTES", n_pad * 512 // pack)
    assert hb_mod._gather_pack(n_pad, C) == pack
    packed, s2, p2 = run()
    assert (s1, s2, p1, p2) == (20, 20, 1, pack)
    assert one.any()
    np.testing.assert_array_equal(one, packed)


def test_gather_pack_follows_the_table_size_and_the_lane_width():
    """The cells' shapes: the plain table (1 vertex a row) at 2^17 ids,
    where it is 64 MiB, 2 vertices a row at 2^18; never more than fit 128
    lanes. A table of ONE column is never plain — its row gather is the
    flat gather, the dear form (docs/KERNELS.md): a full row of lanes (as
    many as divide the ids), however many ids read it — the picked rows,
    128 elements an id read, are taken ``rows_tile`` ids at a time past
    ``PACKED_ROWS_BYTES``: the cells' one-window dispatches whole, an
    int64 leaf at half the ids of a float."""
    from raphtory_tpu.engine.hopbatch import _gather_pack
    from raphtory_tpu.ops.gather import PACKED_ROWS_BYTES, rows_tile

    sizes = (1024, 65_536, 131_072, 262_144, 393_216)
    assert [_gather_pack(n, 6) for n in sizes] == [1, 1, 1, 2, 4]
    assert _gather_pack(1 << 22, 6) == 16 and _gather_pack(1 << 22, 16) == 8
    assert [_gather_pack(n, 1) for n in sizes + (1 << 22,)] == [128] * 6
    assert [_gather_pack(n, 1) for n in (192, 96, 6, 7)] == [64, 32, 2, 1]
    whole = PACKED_ROWS_BYTES // 512
    assert whole == 1 << 22
    assert [rows_tile(ids, 4) for ids in (3_735_552, whole, whole + 1,
                                          3 * 3_735_552, 3 * 7_667_712)
            ] == [3_735_552, whole, whole, whole, whole]
    assert [rows_tile(3 * 4_200_000, size) for size in (8, 4, 1)
            ] == [whole // 2, whole, 3 * 4_200_000]
