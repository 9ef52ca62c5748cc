"""Device runtime plane (obs/device.py, /devicez) — ISSUE 12.

Covers the tentpole surfaces and the satellite hard cases: sampled
timed dispatches joining measured p50/p99 + divergence + bound_measured
to the estimate-side registry rows, the memory_stats degrade path
(None/raising backends must leave /devicez serving ``memory:
unavailable`` — never a 500, never a dead sampler), the
RTPU_KERNEL_REGISTRY_CAP oldest-eviction, compile observability
(xla.compile spans, per-kernel counts, the storm signal), the
weakref-keyed resident-buffer registry, the ledger's measured columns,
and the advisor's two device rules (fire on synthetic evidence, quiet
on this healthy rig).
"""

import gc
import itertools
import json
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raphtory_tpu.obs import advisor as advisor_mod
from raphtory_tpu.obs import device, ledger
from raphtory_tpu.obs.trace import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_device():
    device.clear()
    ledger.REGISTRY.clear()
    ledger.REGISTRY.evictions = 0
    yield
    device.clear()
    ledger.REGISTRY.clear()
    ledger.REGISTRY.evictions = 0


_SEQ = itertools.count(1)


def _kernel(fn=None):
    """A freshly named instrumented kernel per call — registry and
    timing tables key by name, so tests must not share rows."""
    return ledger.instrument(f"test_device.k{next(_SEQ)}",
                             jax.jit(fn or (lambda x: x * 2.0 + 1.0)))


# ------------------------------------------------------------- sampling


def test_timing_rate_knob(monkeypatch):
    monkeypatch.delenv("RTPU_DEVICE_TIMING", raising=False)
    assert device.timing_rate() == device.DEFAULT_RATE
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "0")
    assert device.timing_rate() == 0.0
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "0.5")
    assert device.timing_rate() == 0.5
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "2")
    assert device.timing_rate() == 1.0       # clamped
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "junk")
    assert device.timing_rate() == device.DEFAULT_RATE


def test_sampled_dispatch_records_measured_stats(monkeypatch):
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "1")
    k = _kernel()
    for _ in range(5):
        k(jnp.ones(32))
    rows = [r for r in device.measured_table() if r["kernel"] == k.name]
    assert len(rows) == 1
    m = rows[0]["measured"]
    # dispatch 1 is the cold sample, 2..5 are warm at rate 1
    assert m["samples"] == 4
    assert m.get("cold_seconds") is not None
    assert m["p50_seconds"] > 0
    assert m["p99_seconds"] >= m["p50_seconds"]
    # the estimate join: achieved rates + divergence + re-classification
    # (CPU harvests cost_analysis, so the model side exists here)
    if ledger.xla_analysis_caps()["cost"]:
        assert rows[0].get("divergence", 0) > 0
        assert rows[0]["bound_measured"] in (
            "compute_bound", "hbm_bound", "overhead_bound")
        assert rows[0].get("achieved_flops_per_s", 0) > 0


def test_rate_zero_never_samples(monkeypatch):
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "0")
    k = _kernel()
    for _ in range(4):
        k(jnp.ones(8))
    assert device.TIMING.totals()["kernels_measured"] == 0


def test_sampling_interval_first_two_then_rate(monkeypatch):
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "0.25")
    decisions = [device.TIMING.should_sample("probe", ("f32[8]",))
                 for _ in range(12)]
    # dispatch 1: cold; dispatch 2: warm; then every 4th (n=4,8,12)
    assert decisions[0] == (True, True)
    assert decisions[1] == (True, False)
    timed = [i + 1 for i, (t, _) in enumerate(decisions) if t]
    assert timed == [1, 2, 4, 8, 12]


def test_kernel_registry_cap_evicts_oldest(monkeypatch):
    monkeypatch.setenv("RTPU_KERNEL_REGISTRY_CAP", "4")
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "1")
    k = _kernel()
    for n in range(6):          # 6 distinct shape sigs, one kernel
        k(jnp.ones(8 + n))
    snap = ledger.REGISTRY.snapshot()
    assert len(snap) <= 4
    assert ledger.REGISTRY.evictions >= 2
    # the timing table prunes the same keys (shared cap + evict hook)
    assert device.TIMING.totals()["kernels_measured"] <= 4
    blk = ledger.status_block()
    assert blk["kernel_registry_cap"] == 4
    assert blk["kernel_registry_evictions"] >= 2


def test_registry_eviction_is_lru_and_reharvests(monkeypatch):
    """The cap evicts the COLDEST (kernel, sig) — a hot kernel's row
    (touched every dispatch) survives shape-diverse churn — and an
    evicted key re-harvests on return instead of serving host-mode
    Nones forever."""
    monkeypatch.setenv("RTPU_KERNEL_REGISTRY_CAP", "2")
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "0")
    k = _kernel()
    hot, cold = jnp.ones(16), jnp.ones(24)
    k(hot)
    k(cold)
    k(hot)                      # LRU touch: hot is now the young end
    k(jnp.ones(32))             # third sig → evicts COLD, not hot
    sigs = {r["sig"] for r in ledger.REGISTRY.snapshot()
            if r["kernel"] == k.name}
    assert any("[16]" in s for s in sigs), "hot sig was evicted"
    assert not any("[24]" in s for s in sigs), "cold sig survived"
    # the evicted sig re-registers AND re-harvests when traffic returns
    assert ledger.REGISTRY.needs_harvest(
        k.name, ledger._sig_of((cold,))) is True
    # ...exactly once per live record
    assert ledger.REGISTRY.needs_harvest(
        k.name, ledger._sig_of((cold,))) is False


# -------------------------------------------------------- memory degrade


class _NoStatsDev:
    platform = "cpu"

    def memory_stats(self):
        return None


class _RaisingDev:
    platform = "cpu"

    def memory_stats(self):
        raise RuntimeError("backend has no allocator stats")


@pytest.mark.parametrize("dev", [_NoStatsDev(), _RaisingDev()])
def test_memory_snapshot_degrades_not_raises(monkeypatch, dev):
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
    snap = device.memory_snapshot()
    assert snap["available"] is False
    # the series collector raises BY CONTRACT (ring records None)...
    with pytest.raises(RuntimeError):
        device.series_bytes_in_use()
    # ...the prometheus callback never does
    assert device.gauge_bytes_in_use() == 0.0
    # and the full document keeps serving with the honest degrade
    d = device.devicez()
    assert d["memory"]["available"] is False
    assert "unavailable" in d["memory"]["note"]


def test_series_ring_survives_unavailable_memory(monkeypatch):
    from raphtory_tpu.obs.slo import SeriesRing

    monkeypatch.setattr(jax, "devices",
                        lambda *a, **k: [_RaisingDev()])
    ring = SeriesRing(ring=16)
    row = ring.sample_once()      # must not raise, must record the gap
    assert row["device_bytes_in_use"] is None
    assert row["device_resident_bytes"] == 0.0
    # a second sample proves nothing wedged
    assert ring.sample_once()["device_bytes_in_use"] is None


def test_memory_snapshot_available(monkeypatch):
    class _Dev:
        platform = "tpu"

        def memory_stats(self):
            return {"bytes_in_use": 1000, "peak_bytes_in_use": 2000,
                    "bytes_limit": 10000}

    monkeypatch.setattr(jax, "devices", lambda *a, **k: [_Dev()])
    snap = device.memory_snapshot()
    assert snap == {"available": True, "bytes_in_use": 1000,
                    "peak_bytes_in_use": 2000, "bytes_limit": 10000,
                    "in_use_fraction": 0.1}
    assert device.series_bytes_in_use() == 1000.0
    assert device.gauge_bytes_in_use() == 1000.0


# ------------------------------------------------------ resident registry


class _Owner:
    pass


def test_resident_registry_upsert_drop_and_weakref():
    a, b = _Owner(), _Owner()
    device.RESIDENT.track(a, "edge_tables", 1000, m=7)
    device.RESIDENT.track(a, "edge_tables", 1500)   # upsert, not add
    device.RESIDENT.track(a, "advanced_base", 200)
    device.RESIDENT.track(b, "fold_state", 300)
    snap = device.RESIDENT.snapshot()
    assert snap["total_bytes"] == 2000
    assert {r["kind"] for r in snap["buffers"]} == {
        "edge_tables", "advanced_base", "fold_state"}
    device.RESIDENT.drop(a, "advanced_base")
    assert device.RESIDENT.snapshot()["total_bytes"] == 1800
    del a
    gc.collect()
    snap = device.RESIDENT.snapshot()   # a's rows died with a
    assert snap["total_bytes"] == 300


def test_engines_feed_resident_registry():
    """A DeviceSweep construction lands its edge tables + fold state in
    the registry, and the rows die with the engine/log."""
    from raphtory_tpu.core.events import EventLog
    from raphtory_tpu.engine.device_sweep import DeviceSweep

    log = EventLog()
    rng = np.random.default_rng(5)
    for t, a, b in zip(np.sort(rng.integers(0, 100, 300)),
                       rng.integers(0, 40, 300),
                       rng.integers(0, 40, 300)):
        log.add_edge(int(t), int(a), int(b))
    sweep = DeviceSweep(log)
    kinds = {r["kind"] for r in device.RESIDENT.snapshot()["buffers"]}
    assert {"edge_tables", "fold_state"} <= kinds
    assert device.RESIDENT.snapshot()["total_bytes"] > 0
    del sweep, log
    gc.collect()
    assert device.RESIDENT.snapshot()["total_bytes"] == 0


def test_nbytes_tree():
    a = np.zeros(10, np.int32)
    assert device.nbytes_tree((a, [a, None], a)) == 120
    assert device.nbytes_tree(None) == 0


# ---------------------------------------------------- compile observability


def test_compile_observed_with_span(monkeypatch):
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "0")
    was = TRACER.enabled
    TRACER.enable()
    try:
        k = _kernel()
        k(jnp.ones(64))           # fresh (kernel, sig): harvest compiles
    finally:
        TRACER.enabled = was
    if not ledger.xla_analysis_caps()["cost"]:
        pytest.skip("no AOT harvest on this backend")
    blk = device.compile_block()
    assert k.name in blk
    assert blk[k.name]["compiles"] == 1
    assert blk[k.name]["seconds"] >= 0
    assert "float" in blk[k.name]["last_sig"]
    events = device.recent_compiles()
    assert any(e["kernel"] == k.name for e in events)
    names = {s.get("name") for s in TRACER.recent(400)}
    assert "xla.compile" in names


def test_compile_storm_signal(monkeypatch):
    monkeypatch.setenv("RTPU_ADVISOR_COMPILE_STORM", "3")
    for i in range(4):
        device.note_compile("stormy", f"f32[{i}]", 0.01)
    storm = device.compile_storm()
    assert storm["threshold"] == 3
    assert storm["events_in_window"] == 4
    assert storm["distinct_sigs_in_window"] == 4
    assert storm["storm"] is True


# ------------------------------------------------------------ ledger join


def test_ledger_measured_seconds_and_peak_device_bytes(monkeypatch):
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "1")
    monkeypatch.setattr(
        device, "memory_snapshot",
        lambda: {"available": True, "bytes_in_use": 123_456,
                 "peak_bytes_in_use": 222_222})
    k = _kernel()
    led = ledger.Ledger("q1", "Probe")
    with ledger.activate(led):
        for _ in range(3):
            k(jnp.ones(16))
    led.finish(1.0)
    d = led.as_dict()["device"]
    assert d["timed_dispatches"] >= 1
    assert d["measured_seconds"] > 0
    assert d["peak_device_bytes"] == 123_456
    assert d["kernels"][k.name]["timed_dispatches"] >= 1
    # merge: measured sums, peak maxes
    other = ledger.Ledger("q2")
    other.count_measured(k.name, 0.5)
    other.note_device_memory(999_999)
    led.merge(other)
    d2 = led.as_dict()["device"]
    assert d2["peak_device_bytes"] == 999_999
    assert d2["kernels"][k.name]["measured_seconds"] > 0.5


# ------------------------------------------------------------- advisor


def test_advisor_device_rules_registered():
    ids = {rid for rid, _, _, _ in advisor_mod.RULES}
    assert {"device-model-divergence", "device-pressure"} <= ids


def test_rule_model_divergence_fires_on_inconsistent_ratios():
    def row(kernel, div, samples=8, bound="hbm_bound"):
        return {"kernel": kernel, "sig": "s", "divergence": div,
                "bound_measured": bound,
                "measured": {"samples": samples}}

    sig = {"device": {"timing": [row("a", 1.0), row("b", 100.0)]}}
    f = advisor_mod.rule_model_divergence(sig)
    assert f is not None and f["rule_id"] == "device-model-divergence"
    assert f["knob"] == "RTPU_LEDGER_RIDGE"
    assert f["evidence"]["spread"] > 16

    # consistent ratios — even absolutely huge ones — stay quiet: the
    # platform anchors are order-of-magnitude, constant offset is fine
    sig = {"device": {"timing": [row("a", 40.0), row("b", 55.0)]}}
    assert advisor_mod.rule_model_divergence(sig) is None
    # evidence floors: one kernel / few samples say nothing
    sig = {"device": {"timing": [row("a", 1.0),
                                 row("b", 100.0, samples=2)]}}
    assert advisor_mod.rule_model_divergence(sig) is None
    # overhead_bound rows carry no model-ranking evidence (dispatch
    # overhead dominates — every CPU rig has these): excluded
    sig = {"device": {"timing": [
        row("a", 1.0), row("b", 2000.0, bound="overhead_bound")]}}
    assert advisor_mod.rule_model_divergence(sig) is None


def test_rule_device_pressure_memory_and_storm():
    sig = {"device": {"memory": {"available": True,
                                 "bytes_in_use": 95, "bytes_limit": 100},
                      "compile": {}}}
    f = advisor_mod.rule_device_pressure(sig)
    assert f is not None and f["knob"] == "RTPU_TILE_BUDGET_MB"
    assert f["severity"] == "warning"

    sig = {"device": {"memory": {"available": False},
                      "compile": {"events_in_window": 20,
                                  "distinct_sigs_in_window": 12,
                                  "threshold": 16,
                                  "window_seconds": 60.0}}}
    f = advisor_mod.rule_device_pressure(sig)
    assert f is not None and f["knob"] == "JAX_COMPILATION_CACHE_DIR"

    # healthy: memory unavailable + a few warm-up compiles
    sig = {"device": {"memory": {"available": False},
                      "compile": {"events_in_window": 3,
                                  "distinct_sigs_in_window": 3,
                                  "threshold": 16}}}
    assert advisor_mod.rule_device_pressure(sig) is None


def test_device_rules_quiet_on_this_healthy_rig(monkeypatch):
    """gather_signals → evaluate_rules on the live (CPU, few-kernel)
    process must not fire the device rules — the zero-findings-on-
    healthy-run CI gate covers them."""
    monkeypatch.setenv("RTPU_DEVICE_TIMING", "1")
    k = _kernel()
    for _ in range(6):
        k(jnp.ones(24))
    sig = advisor_mod.gather_signals()
    findings = advisor_mod.evaluate_rules(sig)
    assert not [f for f in findings if f["rule_id"].startswith("device-")]


# ---------------------------------------------------------------- REST


def _graph(n=200):
    from raphtory_tpu.core.service import TemporalGraph
    from raphtory_tpu.ingestion.pipeline import IngestionPipeline
    from raphtory_tpu.ingestion.source import IterableSource
    from raphtory_tpu.ingestion.updates import EdgeAdd

    pipe = IngestionPipeline()
    rng = np.random.default_rng(0)
    updates = [EdgeAdd(int(t), int(a), int(b))
               for t, a, b in zip(np.sort(rng.integers(0, 100, n)),
                                  rng.integers(0, 30, n),
                                  rng.integers(0, 30, n))]
    pipe.add_source(IterableSource(updates, name="test"))
    pipe.run()
    return TemporalGraph(pipe.log, pipe.watermarks)


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=30) as r:
        return json.loads(r.read())


def test_devicez_rest_and_statusz_device_block(monkeypatch):
    import urllib.error

    from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery
    from raphtory_tpu.jobs.rest import RestServer

    monkeypatch.setenv("RTPU_DEVICE_TIMING", "1")
    from raphtory_tpu.jobs import registry as prog_registry

    g = _graph()
    mgr = AnalysisManager(g)
    srv = RestServer(mgr, port=0).start()
    try:
        job = mgr.submit(prog_registry.resolve("PageRank",
                                               {"max_steps": 5}),
                         ViewQuery(90))
        assert job.wait(120) and job.status == "done", job.error

        d = _get(srv.port, "/devicez")
        # this rig has no memory counters: the degrade serves, not 500s
        assert d["memory"]["available"] is False
        assert d["timing"]["kernels_measured"] >= 1
        measured = [r for r in d["timing"]["kernels"]
                    if r["measured"].get("p50_seconds")]
        assert measured, "no kernel carried a measured p50"
        assert "resident" in d and "compile" in d

        st = _get(srv.port, "/statusz")
        assert st["device"]["timing"]["kernels_measured"] >= 1
        assert st["device"]["memory"]["available"] is False
        assert "kernels" in st["compile_caches"]

        cz = _get(srv.port, "/clusterz")
        assert "device" in cz
        me = [p for p in cz["processes"].values() if p.get("self")][0]
        assert me["device"]["timing"]["kernels_measured"] >= 1
    finally:
        srv.stop()


def test_compile_cache_placement(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax owns the directory and the
    code assigns none. Unset: ONE fixed path inside the checkout — never
    a temp name, pid or timestamp (the path is part of the cache key)."""
    import jax

    from raphtory_tpu.utils import config

    old = jax.config.jax_compilation_cache_dir
    platforms = jax.config.jax_platforms
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "/sentinel/untouched")
        assert config.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "/sentinel/untouched"

        # unset, on a process pinned to the CPU (this one): no cache —
        # XLA:CPU executables do not round-trip reliably
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert platforms == "cpu"
        assert config.configure_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir is None

        # unset, platform not pinned to the CPU (the chip): the fixed path
        jax.config.update("jax_platforms", None)   # a flag; backends stay
        fixed = os.path.join(ROOT, ".jax_cache")
        assert config.configure_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert config.configure_compile_cache() == fixed   # every call
        # even sub-second compiles persist
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0
    finally:
        jax.config.update("jax_platforms", platforms)
        jax.config.update("jax_compilation_cache_dir", old)
