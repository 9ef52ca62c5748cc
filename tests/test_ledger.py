"""Resource ledger (obs/ledger.py).

Covers the ISSUE 6 satellites: accumulation/merge across threads (the
parallel fold workers' shape), the instrument()/registry harvest path
with its CPU/capability fallback (degrade to host-side accounting, never
fail a sweep), and the fold-cache-hit hop.fold span + ledger entry.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raphtory_tpu.obs import ledger


@pytest.fixture(autouse=True)
def _fresh_caps():
    """Each test re-probes XLA capabilities under its own env."""
    ledger.reset_xla_caps()
    yield
    ledger.reset_xla_caps()


# ------------------------------------------------------------ Ledger core


def test_ledger_concurrent_accumulation_and_merge():
    led = ledger.Ledger("q", "PR")

    def worker():
        for _ in range(200):
            led.add_phase("fold", 0.001)
            led.add_sweep({}, {}, 0, 0,
                          fold_modes={"parallel": 0.001})
            led.count_views()

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert led.views == 800
    assert abs(led.phase_seconds["fold"] - 0.8) < 1e-9
    assert abs(led.fold_mode_seconds["parallel"] - 0.8) < 1e-9

    # merge: the parallel-fold-unit shape (private ledgers folded in)
    a, b = ledger.Ledger("a"), ledger.Ledger("b")
    a.add_phase("fold", 1.0)
    a.count_dispatch("k", {"flops": 10.0, "bytes_accessed": 100.0,
                           "bound": "hbm_bound"})
    a.fold_cache_event(True)
    b.add_phase("fold", 2.0)
    b.add_phase("compute", 3.0)
    b.count_dispatch("k", {"flops": 5.0, "bytes_accessed": 50.0,
                           "bound": "hbm_bound"})
    b.fold_cache_event(False)
    a.merge(b)
    assert abs(a.phase_seconds["fold"] - 3.0) < 1e-9
    assert abs(a.phase_seconds["compute"] - 3.0) < 1e-9
    assert a.kernels["k"]["dispatches"] == 2
    assert abs(a.kernels["k"]["est_flops"] - 15.0) < 1e-9
    assert a.fold_cache_hits == 1 and a.fold_cache_misses == 1


def test_ledger_finish_other_residual_sums_to_wall():
    led = ledger.Ledger("q")
    led.queue_wait_seconds = 0.5
    led.add_phase("fold", 1.0)
    led.add_phase("compute", 2.0)
    led.finish(5.0)
    d = led.as_dict()
    total = d["queue_wait_seconds"] + sum(d["phase_seconds"].values())
    assert abs(total - 5.0) < 1e-9
    assert d["phase_seconds"]["other"] == pytest.approx(1.5)
    assert d["host"]["peak_rss_bytes"] > 0


def test_query_bound_classification_rules():
    led = ledger.Ledger("q")
    led.add_phase("fold", 10.0)
    led.add_phase("compute", 1.0)
    assert led.bound() == "host_bound"
    led2 = ledger.Ledger("q2")
    led2.add_phase("ship", 10.0)
    led2.add_phase("compute", 1.0)
    assert led2.bound() == "h2d_bound"
    led3 = ledger.Ledger("q3")
    led3.add_phase("compute", 10.0)
    led3.count_dispatch("k", {"flops": 1e6, "bytes_accessed": 1e9,
                              "bound": "hbm_bound"})
    assert led3.bound() == "hbm_bound"


def test_roofline_classifier_rule():
    assert ledger.classify_roofline(None, 100) == "unknown"
    assert ledger.classify_roofline(100, None) == "unknown"
    ridge = ledger.ridge_flops_per_byte("cpu")
    assert ledger.classify_roofline(ridge * 10, 1.0, "cpu") \
        == "compute_bound"
    assert ledger.classify_roofline(ridge * 0.1, 1.0, "cpu") == "hbm_bound"


def test_ridge_override_knob(monkeypatch):
    monkeypatch.setenv("RTPU_LEDGER_RIDGE", "2.5")
    assert ledger.ridge_flops_per_byte("TPU v5 lite") == 2.5


def test_device_peaks_keyed_by_kind_unknown_raises():
    # v5e: Google Cloud "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s
    assert ledger.device_peaks("TPU v5 lite") == (197e12, 819e9)
    assert ledger.device_peaks() == ledger.DEVICE_PEAKS["cpu"]
    with pytest.raises(KeyError, match="TPU v9"):
        ledger.device_peaks("TPU v9")
    with pytest.raises(KeyError):
        ledger.classify_roofline(1e9, 1e6, "tpu")   # a platform, not a kind


# -------------------------------------------------- instrument + registry


def test_instrument_harvests_and_attributes(monkeypatch):
    monkeypatch.setattr(ledger, "REGISTRY", ledger.KernelRegistry())
    fn = ledger.instrument("test.kernel",
                           jax.jit(lambda x: jnp.sum(x * 2.0)))
    led = ledger.Ledger("q")
    with ledger.activate(led):
        fn(jnp.ones((64,), jnp.float32))
        fn(jnp.ones((64,), jnp.float32))
        fn(jnp.ones((128,), jnp.float32))   # second shape signature
    recs = ledger.REGISTRY.snapshot()
    assert len(recs) == 2
    assert sum(r["dispatches"] for r in recs) == 3
    caps = ledger.xla_analysis_caps()
    if caps["cost"]:   # jaxlib supports analysis: harvested + classified
        assert all(r["mode"] == "xla" and r["flops"] is not None
                   for r in recs)
        assert all(r["bound"] in ("hbm_bound", "compute_bound")
                   for r in recs)
    assert led.kernels["test.kernel"]["dispatches"] == 3


def test_instrument_passthrough_when_disabled(monkeypatch):
    monkeypatch.setattr(ledger, "REGISTRY", ledger.KernelRegistry())
    monkeypatch.setenv("RTPU_LEDGER", "0")
    fn = ledger.instrument("test.off", jax.jit(lambda x: x + 1))
    led = ledger.Ledger("q")
    with ledger.activate(led):
        out = fn(jnp.ones((8,)))
        assert ledger.current() is None   # collection gated off
    np.testing.assert_array_equal(np.asarray(out), np.full(8, 2.0))
    assert ledger.REGISTRY.snapshot() == []
    assert led.kernels == {}


def test_capability_probe_degrades_to_host_accounting(monkeypatch):
    """RTPU_LEDGER_XLA=0 (and any probe failure): kernels record in
    host-side mode with bound=unknown — and the dispatch itself is
    untouched (the CPU-fallback regression of the ISSUE satellite)."""
    monkeypatch.setattr(ledger, "REGISTRY", ledger.KernelRegistry())
    monkeypatch.setenv("RTPU_LEDGER_XLA", "0")
    ledger.reset_xla_caps()
    fn = ledger.instrument("test.hostmode", jax.jit(lambda x: x * 3))
    out = fn(jnp.arange(4))
    np.testing.assert_array_equal(np.asarray(out), np.arange(4) * 3)
    (rec,) = ledger.REGISTRY.snapshot()
    assert rec["mode"] == "host" and rec["bound"] == "unknown"
    assert rec["flops"] is None
    caps = ledger.xla_analysis_caps()
    assert not caps["cost"] and not caps["memory"]


def test_harvest_failure_never_fails_the_dispatch(monkeypatch):
    """cost_analysis raising mid-harvest (an exotic backend)
    leaves an error note on the record; the sweep's dispatch result is
    unaffected."""
    monkeypatch.setattr(ledger, "REGISTRY", ledger.KernelRegistry())

    def boom(compiled):
        raise RuntimeError("no analysis on this backend")

    monkeypatch.setattr(ledger, "_cost_dict", boom)
    ledger.reset_xla_caps()   # re-probe under the broken analysis
    fn = ledger.instrument("test.broken", jax.jit(lambda x: x - 1))
    out = fn(jnp.ones((8,)))
    np.testing.assert_array_equal(np.asarray(out), np.zeros(8))
    (rec,) = ledger.REGISTRY.snapshot()
    assert rec["dispatches"] == 1
    assert rec["bound"] == "unknown"


# ------------------------------------------------ engine-level accounting


def _small_log():
    from raphtory_tpu.utils.synth import gab_like_log

    return gab_like_log(n_vertices=150, n_edges=1500, t_span=10_000)


def test_hopbatch_sweep_records_into_active_ledger(monkeypatch):
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "0")   # fold for real
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank

    led = ledger.Ledger("sweep", "PageRank")
    with ledger.activate(led):
        hb = HopBatchedPageRank(_small_log(), max_steps=10)
        ranks, _ = hb.run([4000, 6000, 8000, 10000], [None, 2000])
        np.asarray(ranks)
    d = led.as_dict()
    assert d["sweeps"] == 1 and d["hops"] == 4
    assert set(d["phase_seconds"]) >= {"fold", "stage", "ship", "compute"}
    assert d["fold"]["seconds_by_mode"]   # serial or parallel, host-sized
    assert d["fold"]["cache_misses"] == 0   # cache disabled: never consulted
    assert any(n.startswith("hopbatch.")
               for n in d["device"]["kernels"])
    assert d["device"]["dispatches"] >= 1


def test_fold_cache_hit_emits_span_and_ledger_entry(monkeypatch):
    """The warm-hit satellite: a repeated range sweep serves its fold
    from the cache AND still emits a hop.fold span (mode=cache_hit) plus
    a ledger fold entry — the phase timeline shows where the fold went
    instead of silently omitting the phase."""
    monkeypatch.setenv("RTPU_FOLD_CACHE_MB", "64")
    from raphtory_tpu.core.sweep import fold_cache
    from raphtory_tpu.engine.hopbatch import HopBatchedPageRank
    from raphtory_tpu.obs.trace import TRACER

    fold_cache().clear()
    log = _small_log()
    hops, windows = [4000, 6000, 8000, 10000], [None]

    miss_led = ledger.Ledger("miss")
    with ledger.activate(miss_led):
        r1, _ = HopBatchedPageRank(log, max_steps=10).run(hops, windows)
    assert miss_led.fold_cache_misses == 1
    assert miss_led.fold_cache_hits == 0

    was_enabled = TRACER.enabled
    TRACER.enable()
    try:
        before = TRACER.recorded
        hit_led = ledger.Ledger("hit")
        with ledger.activate(hit_led):
            hb = HopBatchedPageRank(log, max_steps=10)
            r2, _ = hb.run(hops, windows)
        spans = [e for e in TRACER.recent(500)
                 if e.get("name") == "hop.fold"
                 and e.get("args", {}).get("mode") == "cache_hit"]
        assert TRACER.recorded > before
        assert spans, "warm hit must emit the hop.fold span"
        assert spans[-1]["dur"] < 0.1e6   # near-zero duration (µs units)
    finally:
        TRACER.enabled = was_enabled
    assert hit_led.fold_cache_hits == 1
    assert hb.fold_seconds == 0.0          # a hit's fold cost IS zero
    assert "cache_hit" in hit_led.fold_mode_seconds
    # the hit sweep's phases still sum to its wall time (summary built
    # from fold=0 + compute residual)
    d = hit_led.as_dict()
    assert set(d["phase_seconds"]) >= {"fold", "compute"}
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))


def test_disabled_ledger_publishes_nothing(monkeypatch):
    """RTPU_LEDGER=0 must silence every ledger surface — not just the
    engine-side hooks: no /costz recent-query entry, no queries_completed
    tick (the metrics ride the same gate)."""
    monkeypatch.setenv("RTPU_LEDGER", "0")
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery

    before = ledger.status_block()["queries_completed"]
    g = TemporalGraph(_small_log())
    job = AnalysisManager(g).submit(
        PageRank(max_steps=5), ViewQuery(8000, window=4000),
        explain=True, job_id="silent")
    assert job.wait(120) and job.status == "done", job.error
    assert ledger.status_block()["queries_completed"] == before
    assert all(q["query_id"] != "silent" for q in ledger.recent_queries())
    # the ledger itself still closes (explain consumers see wall/status)
    assert job.ledger.wall_seconds > 0


def test_concurrent_jobs_never_share_a_ledger():
    from raphtory_tpu.algorithms import PageRank
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery

    g = TemporalGraph(_small_log())
    mgr = AnalysisManager(g)
    jobs = [mgr.submit(PageRank(max_steps=5), ViewQuery(8000, window=4000),
                       explain=True, job_id=f"iso_{i}")
            for i in range(3)]
    for j in jobs:
        assert j.wait(120) and j.status == "done", j.error
    ledgers = [j.ledger for j in jobs]
    assert len({id(led) for led in ledgers}) == 3
    for j in jobs:
        d = j.ledger.as_dict()
        assert d["query_id"] == j.id       # no cross-attribution
        assert d["views"] == 1
        total = d["queue_wait_seconds"] + sum(d["phase_seconds"].values())
        assert abs(total - d["wall_seconds"]) <= \
            0.05 * d["wall_seconds"] + 1e-6
