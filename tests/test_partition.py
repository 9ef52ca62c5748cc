"""Partition-centric (PCPM) kernel layout — correctness properties.

The binned route must be invariant to the partition count (1, 2, a
non-dividing 7, and auto), BITWISE equal to the unbinned route on
integer/min-plus reductions (CC labels, BFS depths — min is order-exact),
and tolerance-equal on float sums (PageRank ranks — binned edges sum in a
different order), over adversarial logs with deletes and tombstones.
Plus: layout structural invariants, the engine-order fallback under
``RTPU_PCPM=0`` staying bit-identical to HEAD's kernels, residency
transitions when the knob flips between batches, the partition-blocked
segment reduce, the bsp/features routes, and the ledger traffic model.
"""

import numpy as np
import pytest

from raphtory_tpu.engine.hopbatch import (HopBatchedBFS, HopBatchedCC,
                                          HopBatchedPageRank,
                                          HopBatchedSSSP)
from raphtory_tpu.ops import partition as part

from test_sweep import random_log

HOPS = [20, 45, 46, 79]
WINDOWS = [100, 30, None]


def _log(seed=0, n_events=600, n_ids=40, t_span=80):
    return random_log(np.random.default_rng(seed), n_events=n_events,
                      n_ids=n_ids, t_span=t_span)


# ---------------------------------------------------------------------------
# layout structural invariants


def test_layout_invariants_non_dividing_partitions():
    log = _log(3)
    hb = HopBatchedPageRank(log)
    t = hb.tables
    for P in (1, 2, 7, 16):
        lay = part.build_layout(t.e_src, t.e_dst, t.n_pad, t.m, P)
        s = lay.spec
        assert s.partitions == min(P, t.n_pad)
        assert s.n_per * s.partitions >= t.n_pad
        # every real edge appears exactly once
        assert int(lay.valid.sum()) == t.m
        real = lay.perm[lay.valid]
        assert len(np.unique(real)) == t.m
        assert set(real.tolist()) == set(range(t.m))
        # binned endpoints match the engine table through the permutation
        assert np.array_equal(lay.b_src[lay.valid], t.e_src[real])
        assert np.array_equal(lay.b_dst[lay.valid], t.e_dst[real])
        # destinations live in their slot's partition
        slot_part = np.nonzero(lay.valid)[0] // s.cap
        assert np.array_equal(lay.b_dst[lay.valid] // s.n_per, slot_part)
        # pre-agg buckets decode back to the slot's source
        assert np.array_equal(lay.u_src[lay.slot[lay.valid]],
                              lay.b_src[lay.valid])
        # inverse permutation round-trips (real edges only)
        assert np.array_equal(lay.inv[real],
                              np.nonzero(lay.valid)[0].astype(np.int32))


def test_remap_positions_preserves_drop_sentinel():
    log = _log(1)
    hb = HopBatchedPageRank(log)
    t = hb.tables
    lay = part.build_layout(t.e_src, t.e_dst, t.n_pad, t.m, 4)
    sent = np.int32(2**31 - 1)
    pos = np.array([[0, min(3, t.m - 1), sent], [sent, sent, 1]], np.int32)
    out = lay.remap_positions(pos)
    assert out.shape == pos.shape
    assert (out[pos == sent] == sent).all()
    assert (out[pos != sent] == lay.inv[pos[pos != sent]]).all()


def test_partition_count_auto_and_override():
    budget = 256 << 20
    assert part.partition_count(32768, budget) == 16   # 2048-row slices
    assert part.partition_count(1024, budget) == 1
    assert part.partition_count(32768, budget, override=7) == 7
    assert part.partition_count(8, budget, override=1000) == 8  # clamped


class _Owner:
    """A weakref-able cache key for ``part.resolve`` over bare tables."""


def _ring_tables(m: int):
    """``m`` distinct pairs, dst-sorted, four to a vertex."""
    n = max(m // 4, 8)
    dst = np.repeat(np.arange(n, dtype=np.int32), 4)[:m]
    src = ((dst.astype(np.int64) * 7 + np.tile(np.arange(1, 5), n)[:m])
           % n).astype(np.int32)
    return part.HostTables(src, dst, n, m)


@pytest.mark.parametrize("mode,binned", [
    (None, False), ("", False), ("auto", False), ("0", False),
    ("2", False), ("true", False), ("1", True)])
def test_layout_only_when_asked_for_by_name(monkeypatch, mode, binned):
    """The rule of PR 29: no size picks the binned route. Only
    ``RTPU_PCPM=1`` resolves a layout, at 2^10 pairs and at 2^20 (past
    the 2^17 the old ``auto`` binned from); everything else — unset,
    empty, ``auto``, ``0``, typos — keeps the dst-sorted pair table."""
    if mode is None:
        monkeypatch.delenv("RTPU_PCPM", raising=False)
    else:
        monkeypatch.setenv("RTPU_PCPM", mode)
    assert part.pcpm_enabled() is binned
    for m in (1 << 10, 1 << 20):
        t = _ring_tables(m)
        lay = part.resolve(_Owner(), t, 256 << 20)
        if not binned:
            assert lay is None
        else:
            assert lay.m == m and int(lay.valid.sum()) == m
            assert lay.spec.partitions == part.partition_count(
                t.n_pad, 256 << 20)


# ---------------------------------------------------------------------------
# partition-count invariance over adversarial delete/tombstone logs


def _run(cls_args, hops=HOPS, windows=WINDOWS, **kw):
    cls, args, ctor = cls_args
    hb = cls(*args, **ctor)
    out, steps = hb.run(hops, windows, **kw)
    return np.asarray(out)


@pytest.mark.parametrize("seed", [0, 7])
def test_pagerank_invariant_to_partition_count(monkeypatch, seed):
    log = _log(seed)
    spec = (HopBatchedPageRank, (log,), dict(tol=1e-7, max_steps=20))
    monkeypatch.setenv("RTPU_PCPM", "0")
    want = _run(spec)
    monkeypatch.setenv("RTPU_PCPM", "1")
    for P in ("1", "2", "7", None):   # None = auto sizing
        if P is None:
            monkeypatch.delenv("RTPU_PARTITIONS", raising=False)
        else:
            monkeypatch.setenv("RTPU_PARTITIONS", P)
        got = _run(spec)
        # float sums reorder across the binned segments — tolerance, the
        # documented contract (docs/KERNELS.md)
        np.testing.assert_allclose(got, want, atol=2e-6, rtol=0,
                                   err_msg=f"P={P}")


@pytest.mark.parametrize("seed", [1, 9])
def test_cc_bitwise_invariant_to_partition_count(monkeypatch, seed):
    log = _log(seed, n_events=500, n_ids=35, t_span=70)
    spec = (HopBatchedCC, (log,), dict(max_steps=60))
    monkeypatch.setenv("RTPU_PCPM", "0")
    want = _run(spec, hops=[25, 69], windows=[100, 20])
    monkeypatch.setenv("RTPU_PCPM", "1")
    for P in ("1", "2", "7", None):
        if P is None:
            monkeypatch.delenv("RTPU_PARTITIONS", raising=False)
        else:
            monkeypatch.setenv("RTPU_PARTITIONS", P)
        got = _run(spec, hops=[25, 69], windows=[100, 20])
        # min-label propagation is order-exact: BITWISE equality
        assert np.array_equal(got, want), f"P={P}"


@pytest.mark.parametrize("directed", [False, True])
def test_bfs_bitwise_invariant_to_partition_count(monkeypatch, directed):
    log = _log(6, n_events=400, n_ids=30, t_span=60)
    spec = (HopBatchedBFS, (log, (0, 1, 2)),
            dict(directed=directed, max_steps=40))
    monkeypatch.setenv("RTPU_PCPM", "0")
    want = _run(spec, hops=[25, 59], windows=[100, 15])
    monkeypatch.setenv("RTPU_PCPM", "1")
    for P in ("2", "7"):
        monkeypatch.setenv("RTPU_PARTITIONS", P)
        got = _run(spec, hops=[25, 59], windows=[100, 15])
        assert np.array_equal(got, want), f"P={P}"


def test_weighted_sssp_invariant_under_pcpm(monkeypatch):
    from raphtory_tpu.core.events import EventLog

    rng = np.random.default_rng(4)
    log = EventLog()
    for i in range(400):
        s, d = int(rng.integers(0, 25)), int(rng.integers(0, 25))
        log.add_edge(int(rng.integers(0, 60)), s, d,
                     {"w": float(rng.uniform(0.5, 3.0))})
        if rng.random() < 0.15:
            log.delete_edge(int(rng.integers(0, 60)), s, d)
    spec = (HopBatchedSSSP, (log, (0, 1), "w"), dict(max_steps=40))
    monkeypatch.setenv("RTPU_PCPM", "0")
    want = _run(spec, hops=[20, 59], windows=[100, 25])
    monkeypatch.setenv("RTPU_PCPM", "1")
    monkeypatch.setenv("RTPU_PARTITIONS", "3")
    got = _run(spec, hops=[20, 59], windows=[100, 25])
    # min-plus over identical binned weights: bitwise
    assert np.array_equal(got, want)


def test_chunked_resident_batches_under_pcpm(monkeypatch):
    """Chunked pipelined sweeps + a follow-on forward batch keep the
    device-resident advanced base BINNED across dispatches."""
    log = _log(11, n_events=700, n_ids=45, t_span=100)
    # a shared-fold-cache hit would (correctly) drop residency — disable
    # the cache so this test exercises the resident binned base itself
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")
    monkeypatch.setenv("RTPU_PCPM", "0")
    hb0 = HopBatchedPageRank(log, tol=1e-7, max_steps=20)
    w1 = np.asarray(hb0.run([20, 40, 60, 80], [50, None], chunks=2)[0])
    w2 = np.asarray(hb0.run([90, 99], [50, None])[0])
    monkeypatch.setenv("RTPU_PCPM", "1")
    monkeypatch.setenv("RTPU_PARTITIONS", "5")
    hb1 = HopBatchedPageRank(log, tol=1e-7, max_steps=20)
    g1 = np.asarray(hb1.run([20, 40, 60, 80], [50, None], chunks=2)[0])
    assert hb1._dev_base is not None and hb1._dev_base_spec is not None
    g2 = np.asarray(hb1.run([90, 99], [50, None])[0])
    np.testing.assert_allclose(g1, w1, atol=2e-6, rtol=0)
    np.testing.assert_allclose(g2, w2, atol=2e-6, rtol=0)


def test_knob_flip_between_batches_drops_residency(monkeypatch):
    """A resident base built by one layout must not receive the other
    layout's catch-up delta — flipping RTPU_PCPM between forward batches
    re-ships a fresh base and stays correct (both flip directions)."""
    log = _log(13, n_events=700, n_ids=45, t_span=100)
    monkeypatch.setenv("RTPU_PCPM", "0")
    ref = HopBatchedCC(log, max_steps=60)
    w1 = np.asarray(ref.run([30, 50], [60])[0])
    w2 = np.asarray(ref.run([70, 99], [60])[0])

    monkeypatch.setenv("RTPU_PCPM", "1")
    monkeypatch.setenv("RTPU_PARTITIONS", "4")
    hb = HopBatchedCC(log, max_steps=60)
    g1 = np.asarray(hb.run([30, 50], [60])[0])
    spec_before = hb._dev_base_spec
    assert spec_before is not None
    monkeypatch.setenv("RTPU_PCPM", "0")       # flip: binned → engine
    g2 = np.asarray(hb.run([70, 99], [60])[0])
    assert hb._dev_base_spec is None
    assert np.array_equal(g1, w1) and np.array_equal(g2, w2)

    monkeypatch.setenv("RTPU_PCPM", "0")
    hb2 = HopBatchedCC(log, max_steps=60)
    h1 = np.asarray(hb2.run([30, 50], [60])[0])
    monkeypatch.setenv("RTPU_PCPM", "1")       # flip: engine → binned
    h2 = np.asarray(hb2.run([70, 99], [60])[0])
    assert np.array_equal(h1, w1) and np.array_equal(h2, w2)


def test_tiled_binned_route_matches(monkeypatch):
    """The edge-tiled (budget-bounded) scan works over the binned arrays
    too — pre-agg is bypassed, the permuted operands tile like the
    engine-order ones."""
    log = _log(17, n_events=900, n_ids=60, t_span=90)
    monkeypatch.setenv("RTPU_PCPM", "0")
    want = _run((HopBatchedPageRank, (log,), dict(tol=1e-7, max_steps=20)))
    monkeypatch.setenv("RTPU_PCPM", "1")
    monkeypatch.setenv("RTPU_PARTITIONS", "4")
    import raphtory_tpu.engine.hopbatch as hb_mod

    real = hb_mod._edge_tile_for

    def tiny(m_pad, C, budget_bytes):
        if budget_bytes is None:
            return real(m_pad, C, budget_bytes)
        step = 1 << 16
        return min(step, m_pad) if m_pad > 64 else None

    monkeypatch.setattr(hb_mod, "_edge_tile_for", tiny)
    got = _run((HopBatchedPageRank, (log,), dict(tol=1e-7, max_steps=20)))
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)


def test_host_column_fold_path_under_pcpm(monkeypatch):
    """RTPU_FOLD=host ships [H, m_pad] columns; the kernels bin them
    in-program through the layout permutation."""
    log = _log(19)
    monkeypatch.setenv("RTPU_FOLD", "host")
    monkeypatch.setenv("RTPU_PCPM", "0")
    want = _run((HopBatchedCC, (log,), dict(max_steps=60)))
    monkeypatch.setenv("RTPU_PCPM", "1")
    monkeypatch.setenv("RTPU_PARTITIONS", "7")
    got = _run((HopBatchedCC, (log,), dict(max_steps=60)))
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# ops-level partition-blocked reduce


def test_partition_segment_reduce_matches_flat():
    import jax
    import jax.numpy as jnp

    from raphtory_tpu.ops.segment import partition_segment_reduce

    rng = np.random.default_rng(2)
    P, cap, n_per, n = 5, 48, 16, 77          # P*n_per = 80 > n: overhang
    data = rng.integers(-50, 50, (P, cap)).astype(np.int32)
    loc = rng.integers(0, n_per, (P, cap)).astype(np.int32)
    mask = rng.random((P, cap)) < 0.75
    flat_ids = (loc + np.arange(P)[:, None] * n_per).reshape(-1)
    for op, seg in (("sum", jax.ops.segment_sum),
                    ("min", jax.ops.segment_min),
                    ("max", jax.ops.segment_max)):
        from raphtory_tpu.ops.segment import neutral

        flat = np.where(mask.reshape(-1), data.reshape(-1),
                        int(neutral(op, jnp.int32)))
        want = np.asarray(seg(jnp.asarray(flat), jnp.asarray(flat_ids),
                              num_segments=P * n_per))[:n]
        got = np.asarray(partition_segment_reduce(
            jnp.asarray(data), jnp.asarray(loc), n_per, n, op,
            jnp.asarray(mask)))
        assert got.shape == (n,)
        assert np.array_equal(got, want), op
    with pytest.raises(ValueError, match="unknown combiner"):
        partition_segment_reduce(jnp.asarray(data), jnp.asarray(loc),
                                 n_per, n, "mean")


# ---------------------------------------------------------------------------
# bsp + features routes


def test_bsp_exchange_under_pcpm(monkeypatch):
    from raphtory_tpu.algorithms import ConnectedComponents, PageRank
    from raphtory_tpu.core.snapshot import build_view
    from raphtory_tpu.engine import bsp

    log = _log(23)
    view = build_view(log, 60)
    pr = PageRank(max_steps=20, tol=1e-7)
    cc = ConnectedComponents(max_steps=50)
    monkeypatch.setenv("RTPU_PCPM", "0")
    pr0, _ = bsp.run(pr, view, windows=[100, 30, -1])
    cc0, _ = bsp.run(cc, view, windows=[100])
    monkeypatch.setenv("RTPU_PCPM", "1")
    monkeypatch.setenv("RTPU_PARTITIONS", "7")
    pr1, _ = bsp.run(pr, view, windows=[100, 30, -1])
    cc1, _ = bsp.run(cc, view, windows=[100])
    np.testing.assert_allclose(np.asarray(pr1), np.asarray(pr0),
                               atol=2e-6, rtol=0)
    assert np.array_equal(np.asarray(cc1), np.asarray(cc0))
    # the resolved layout carries the dispatch-time spec and bins only
    # the REAL edge rows — the pow2 pad tail must be cap-pad slots, not
    # edges inflating the last partition's capacity
    lay = bsp._view_layout(view, view.e_src, view.e_dst, False)
    assert lay is not None and lay.spec.partitions == 7
    assert lay.m == view.m_active
    assert int(lay.valid.sum()) == view.m_active


def test_features_propagate_under_pcpm(monkeypatch):
    from raphtory_tpu.engine.device_sweep import DeviceSweep
    from raphtory_tpu.engine.features import FeatureAggregator

    log = _log(29)
    ds = DeviceSweep(log)
    ds.advance(60)
    fa = FeatureAggregator(ds, feature_dim=16)
    X = fa.random_features(1)
    monkeypatch.setenv("RTPU_PCPM", "0")
    want = np.asarray(fa.propagate(X, window=50, rounds=2))
    assert fa._pcpm_layout() is None
    # traffic_bytes reports the LAST dispatch's mode (a pure read)
    off_b = fa.traffic_bytes(2)
    monkeypatch.setenv("RTPU_PCPM", "1")
    monkeypatch.setenv("RTPU_PARTITIONS", "3")
    lay = fa._pcpm_layout()
    assert lay is not None and lay.spec.partitions == 3
    got = np.asarray(fa.propagate(X, window=50, rounds=2))
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)
    on_b = fa.traffic_bytes(2)
    if lay.spec.cap_u < lay.spec.cap:    # buckets dedup at all
        assert on_b != off_b


# ---------------------------------------------------------------------------
# ledger traffic model


def test_traffic_model_binned_reduces_est_hbm():
    """The partition-aware DRAM model must claim a reduction for a
    cache-overflowing destination state with well-sized partitions — the
    acceptance evidence the bench records per kernel."""
    m_pad, n_pad = 327_680, 32_768
    lay_spec = part.PartitionSpec(partitions=16, n_per=2048, cap=20_672,
                                  cap_u=13_696, preagg=True)
    for C in (3, 12, 36):
        un = part.edge_traffic_model(m_pad, C, n_pad, None)
        bn = part.edge_traffic_model(m_pad, C, n_pad, lay_spec)
        assert bn["est_hbm_bytes"] < un["est_hbm_bytes"], C
    # cache-resident destination state: no random-access inflation, the
    # unbinned route is already streaming — model must not reward binning
    tiny = part.edge_traffic_model(4096, 4, 256, None)
    assert tiny["est_hbm_bytes"] <= 4096 * (2 * 4 + 4) + 3 * 4096 * 16


def test_instrument_records_refined_fields(monkeypatch):
    import jax
    import jax.numpy as jnp

    from raphtory_tpu.obs import ledger as ledger_mod

    monkeypatch.setenv("RTPU_LEDGER", "1")
    traffic = {"model": "pcpm_superstep", "est_hbm_bytes": 12_345}
    fn = ledger_mod.instrument("test.pcpm_traffic",
                               jax.jit(lambda x: x * 2.0), traffic=traffic)
    out = fn(jnp.arange(8, dtype=jnp.float32))
    jax.block_until_ready(out)
    rec = [r for r in ledger_mod.REGISTRY.snapshot()
           if r["kernel"] == "test.pcpm_traffic"][0]
    assert rec["est_hbm_bytes"] == 12_345
    assert rec["traffic_model"]["model"] == "pcpm_superstep"
    if rec["mode"] == "xla":                   # harvest available
        assert rec["bound_refined"] in ("hbm_bound", "compute_bound")
        # the raw XLA harvest stays untouched next to the model
        assert rec["bytes_accessed"] != rec["est_hbm_bytes"]
    # /costz surfaces both classifications
    cz = ledger_mod.costz()
    assert "kernels_by_bound_refined" in cz
    assert "est_hbm_bytes" in cz["classification_rule"] \
        or "est_hbm_bytes" in str(cz["classification_rule"])


# ---------------------------------------------------------------------------
# PR 29: with the knob unset every route runs the dst-sorted pair table


def _layout_spans(fn):
    """Run ``fn`` under the flight recorder; its ``engine.layout`` spans."""
    from raphtory_tpu.obs.trace import TRACER

    was = TRACER.enabled
    TRACER.enable()
    try:
        seen = TRACER.recorded
        out = fn()
        new = TRACER.recent(TRACER.recorded - seen)
    finally:
        (TRACER.enable if was else TRACER.disable)()
    return out, [e for e in new if e["name"] == "engine.layout"]


@pytest.mark.parametrize("size", ["past_old_threshold", "small"])
def test_unset_knob_dispatches_on_the_sorted_table(monkeypatch, size):
    """No layout is resolved, built or uploaded for a columnar run unless
    it is asked for — also on a log the old ``auto`` would have binned
    (``m_pad`` >= 2^17). The ``engine.layout`` spans are the engagement
    counter: ``partitions`` says which table served the dispatch."""
    from raphtory_tpu.utils.synth import gab_like_log

    monkeypatch.delenv("RTPU_PCPM", raising=False)
    if size == "small":
        log, hops, windows = _log(5), HOPS, WINDOWS
    else:
        log = gab_like_log(n_vertices=6000, n_edges=150_000, t_span=1000)
        hops, windows = [700, 900], [1000, 200]
    hb = HopBatchedPageRank(log, tol=0, max_steps=3)
    assert (hb.tables.m_pad >= 1 << 17) == (size != "small")
    (out, steps), spans = _layout_spans(lambda: hb.run(hops, windows))
    assert hb._active_layout is None
    assert np.asarray(out).shape == (len(hops) * len(windows),
                                     hb.tables.n_pad)
    assert {s["args"]["stage"] for s in spans} == {"resolve", "payload"}
    assert {s["args"]["partitions"] for s in spans} == {0}
    # asked for by name, an engine over the same log bins it
    monkeypatch.setenv("RTPU_PCPM", "1")
    hb1 = HopBatchedPageRank(log, tol=0, max_steps=3)
    (out1, _), spans1 = _layout_spans(lambda: hb1.run(hops, windows))
    assert hb1._active_layout is not None
    assert {s["args"]["partitions"] for s in spans1} \
        == {hb1._active_layout.spec.partitions}
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out),
                               atol=2e-6, rtol=0)


def test_one_chip_default_route_is_the_mesh_routes_table(monkeypatch):
    """Knob unset and ``0`` are one route, bit for bit, and it is the
    table ``run_columns_sharded`` runs on the mesh: the two agree to the
    tolerance tests/test_columns_sharded.py holds them to."""
    import jax

    from raphtory_tpu.parallel.columns import run_columns_sharded

    log = random_log(np.random.default_rng(3), n_events=900, n_ids=50,
                     t_span=100)
    hops, windows = [20, 40, 60, 80, 99], [1000, 25]

    def one_chip():
        hb = HopBatchedPageRank(log, tol=1e-7, max_steps=20)
        out, steps = hb.run(hops, windows)
        assert hb._active_layout is None
        return np.asarray(out), int(steps)

    monkeypatch.delenv("RTPU_PCPM", raising=False)
    unset, steps1 = one_chip()
    hb = HopBatchedPageRank(log, tol=1e-7, max_steps=20)
    _, cols = hb._fold_columns([int(x) for x in hops])
    many, steps2 = run_columns_sharded(
        hb.tables, *cols, hops, windows, jax.devices()[:4],
        tol=1e-7, max_steps=20)
    monkeypatch.setenv("RTPU_PCPM", "0")
    zero, steps0 = one_chip()
    assert np.array_equal(unset, zero) and steps0 == steps1
    np.testing.assert_allclose(unset, np.asarray(many),
                               rtol=1e-5, atol=1e-7)
    assert steps1 == steps2


def test_cold_view_default_passes_no_binned_operands(monkeypatch):
    """``bsp.run_async`` with the knob unset compiles the flat exchange
    (``pcpm`` None in the program's cache key) and hands it no
    ``b_perm`` / ``b_valid`` / ``b_dst``; ``RTPU_PCPM=1`` adds the three
    and agrees to reduction-order tolerance."""
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.core.snapshot import build_view
    from raphtory_tpu.engine import bsp

    calls = []
    real = bsp._compiled_runner

    def spy(*key):
        runner = real(*key)

        def run(*operands):
            calls.append((key[-1], len(operands)))
            return runner(*operands)
        return run

    monkeypatch.setattr(bsp, "_compiled_runner", spy)
    view = build_view(_log(23), 60)
    pr = PageRank(max_steps=20, tol=1e-7)
    monkeypatch.delenv("RTPU_PCPM", raising=False)
    assert bsp._view_layout(view, view.e_src, view.e_dst, False) is None
    flat, _ = bsp.run(pr, view, windows=[100, 30, -1])
    monkeypatch.setenv("RTPU_PCPM", "1")
    binned, _ = bsp.run(pr, view, windows=[100, 30, -1])
    (spec0, n0), (spec1, n1) = calls
    assert spec0 is None and isinstance(spec1, part.PartitionSpec)
    assert n1 == n0 + 3
    np.testing.assert_allclose(np.asarray(binned), np.asarray(flat),
                               atol=2e-6, rtol=0)
