"""CPU tests of chip_smoke.py's own plumbing and of the repairs it needs:
its plain numpy reference agrees with the engine on a log with deletes and
revivals, the rehearsal runs every phase but can never pass, without a
chip the smoke fails; the compile cache is placed from outside, a late
source joins without a replay, and a device error fails the job while a
transport error still declines."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from raphtory_tpu.algorithms import (ConnectedComponents, DegreeBasic,
                                     PageRank)
from raphtory_tpu.core import events as ev
from raphtory_tpu.core.events import EventLog
from raphtory_tpu.core.snapshot import build_view
from raphtory_tpu.engine import bsp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _adversarial_stream(seed, n_events=600, n_ids=14, t_span=60):
    """Time-sorted stream with heavy id reuse, duplicate timestamps,
    vertex/edge deletes and re-adds (revivals)."""
    rng = np.random.default_rng(seed)
    kind_map = np.array([ev.VERTEX_ADD, ev.VERTEX_DELETE, ev.EDGE_ADD,
                         ev.EDGE_DELETE], np.uint8)
    k = kind_map[rng.choice(4, n_events, p=[0.2, 0.1, 0.5, 0.2])]
    t = np.sort(rng.integers(0, t_span, n_events)).astype(np.int64)
    s = rng.integers(0, n_ids, n_events).astype(np.int64)
    d = rng.integers(0, n_ids, n_events).astype(np.int64)
    d[(k == ev.VERTEX_ADD) | (k == ev.VERTEX_DELETE)] = -1
    log = EventLog()
    log.append_batch(t, k, s, d)
    ref = chip_smoke.RefEvents(
        t, k == ev.VERTEX_ADD, k == ev.VERTEX_DELETE, k == ev.EDGE_ADD,
        k == ev.EDGE_DELETE, s, np.maximum(d, 0), n_ids=n_ids)
    return log, ref


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_fold_and_algorithms_match_engine(seed):
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
            # the fold itself: same vertices, same edges
            assert sorted(view.vids[v_mask]) == \
                np.flatnonzero(vm).tolist()
            got_e = sorted(zip(view.vids[view.e_src[e_mask]].tolist(),
                               view.vids[view.e_dst[e_mask]].tolist()))
            assert got_e == sorted(zip(src.tolist(), dst.tolist()))
            saw_dead_edge |= len(src) < len(ref.us)

            pr = PageRank(tol=1e-9, max_steps=200)
            ranks, _ = bsp.run(pr, view, window=w)
            want = chip_smoke.ref_pagerank(vm, src, dst)
            got = np.zeros(ref.n_ids)
            got[view.vids[view.v_mask]] = np.asarray(ranks)[view.v_mask]
            np.testing.assert_allclose(got, want, atol=6e-9, rtol=2e-4)
            chip_smoke.compare_pagerank(
                pr.reduce(ranks, view, window=w),
                chip_smoke.expect_pagerank(vm, src, dst), 1e-9, "test")

            cc = ConnectedComponents()
            labels, _ = bsp.run(cc, view, window=w)
            want_cc = chip_smoke.expect_cc(vm, src, dst)
            assert cc.reduce(labels, view, window=w) == want_cc

            deg = DegreeBasic()
            res, _ = bsp.run(deg, view, window=w)
            assert deg.reduce(res, view, window=w) == \
                chip_smoke.expect_degree(vm, src, dst)
    assert saw_dead_edge, "the stream never killed an edge"


def test_compare_pagerank_rejects_a_wrong_answer():
    r = {"sum": 1.0, "positive": 4,
         "lead": [(0, 0.4), (1, 0.3), (2, 0.2), (3, 0.1)]}
    good = {"sum": 1.0, "top10": [(0, 0.4), (1, 0.3), (2, 0.2), (3, 0.1)]}
    chip_smoke.compare_pagerank(good, r, 1e-9, "ok")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_pagerank(
            {**good, "top10": [(0, 0.41), (1, 0.3), (2, 0.2), (3, 0.1)]},
            r, 1e-9, "moved")
    with pytest.raises(chip_smoke.SmokeFailure):   # a missing leader
        chip_smoke.compare_pagerank(
            {"sum": 1.0, "top10": [(1, 0.3), (2, 0.2), (3, 0.1)]},
            {**r, "positive": 3}, 1e-9, "missing")
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.compare_exact({"clusters": 2}, {"clusters": 3}, "cc")


def _run_smoke(*args, env=None):
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=600, cwd=ROOT,
        env={**os.environ, **(env or {})})


def test_without_a_chip_the_smoke_fails_and_prints_no_result():
    out = _run_smoke(env={"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_rehearsal_runs_every_phase_and_never_prints_the_pass_line():
    out = _run_smoke("--cpu-rehearsal", "--cpu-devices", "4")
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()]
    phases = [r.get("phase") for r in rows]
    for want in ("ingest_bulk", "view_first", "view_warm", "view_cold_bsp",
                 "range_pagerank", "range_cc", "range_degree",
                 "live_under_ingest", "mesh_view_cc_sparse", "mesh_spread",
                 "end"):
        assert want in phases, f"phase {want} missing: {phases}"
    assert all(r["device"] == "cpu" for r in rows[:-1])
    assert rows[-1]["ok"] is False          # the pass line says true
    assert '"ok": true' not in out.stdout


# ------------------------------------------- the repairs the smoke needs


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


def test_late_source_joins_without_replaying_the_drained_one():
    from raphtory_tpu.cluster.runtime import NodeRuntime
    from raphtory_tpu.ingestion.source import IterableSource
    from raphtory_tpu.ingestion.updates import EdgeAdd
    from raphtory_tpu.utils.config import Settings

    rt = NodeRuntime(settings=Settings(rest_port=0, metrics_port=0))
    rt.add_source(IterableSource(
        [EdgeAdd(t, t, t + 1) for t in range(1, 6)], name="first"))
    rt.ingest(wait=True)
    assert len(rt.graph.log) == 5
    rt.add_source(IterableSource(
        [EdgeAdd(t, t, t + 1) for t in range(6, 9)], name="late"))
    rt.ingest(wait=True)
    assert rt.pipeline.counts == {"first": 5, "late": 3}
    assert len(rt.graph.log) == 8          # the first was not replayed
    assert not rt.pipeline.errors


def test_declinable_is_transport_or_oom_only():
    from raphtory_tpu.jobs.manager import declinable
    from raphtory_tpu.resilience.faults import FaultError

    class XlaRuntimeError(RuntimeError):
        pass

    assert declinable(XlaRuntimeError("UNAVAILABLE: connection lost"))
    assert declinable(FaultError("UNAVAILABLE: injected fault"))
    assert declinable(MemoryError())
    assert declinable(XlaRuntimeError("RESOURCE_EXHAUSTED: out of HBM"))
    assert not declinable(XlaRuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel"))
    assert not declinable(TypeError("a bug"))


def _small_graph():
    from raphtory_tpu.core.service import TemporalGraph

    log = EventLog()
    rng = np.random.default_rng(7)
    for t in range(1, 80):
        a, b = (int(x) for x in rng.integers(0, 12, 2))
        log.add_edge(t, a, b)
    return TemporalGraph(log)


@pytest.mark.parametrize("route", ["range_columnar", "live_epoch"])
@pytest.mark.parametrize("error, status", [
    ("INTERNAL: compiler refused the program", "failed"),
    ("UNAVAILABLE: connection lost", "done")])
def test_device_error_fails_the_job_transport_error_declines(
        monkeypatch, route, error, status):
    """A columnar Range or a Live epoch that hits a device error ends
    ``failed`` with that error; a transport error still declines to the
    next rung and the job ends ``done``."""
    from raphtory_tpu.engine.hopbatch import HopBatchedCC
    from raphtory_tpu.jobs import registry
    from raphtory_tpu.jobs.manager import (AnalysisManager, LiveQuery,
                                           RangeQuery)

    class XlaRuntimeError(RuntimeError):
        pass

    def boom(self, *a, **k):
        raise XlaRuntimeError(error)

    monkeypatch.setattr(HopBatchedCC, "run", boom)
    mgr = AnalysisManager(_small_graph())
    q = (RangeQuery(20, 60, 20) if route == "range_columnar"
         else LiveQuery(repeat=20, event_time=True, max_runs=2))
    job = mgr.submit(registry.resolve("ConnectedComponents"), q)
    assert job.wait(120)
    assert job.status == status, job.error
    if status == "failed":
        assert error in job.error and not job.results
    else:
        assert len(job.results) == (3 if route == "range_columnar" else 2)
