"""Durable telemetry journal + postmortem plane: CRC framing and torn
tails, segment rotation under the byte cap, concurrent non-blocking
writers, zero-overhead-off, /journalz + /clusterz surfaces, exitdump
consolidation, rtpu-postmortem replay (ISSUE 18)."""

import json
import threading

import numpy as np
import pytest

from raphtory_tpu.analysis import postmortem
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.ingestion.pipeline import IngestionPipeline
from raphtory_tpu.ingestion.source import IterableSource
from raphtory_tpu.ingestion.updates import EdgeAdd
from raphtory_tpu.obs import cluster as cl
from raphtory_tpu.obs import exitdump
from raphtory_tpu.obs import journal
from raphtory_tpu.obs.trace import TRACER


@pytest.fixture(autouse=True)
def _journal_state(monkeypatch):
    """Every test starts journal-off with a fresh singleton, and leaves
    nothing armed for the rest of the suite."""
    monkeypatch.delenv("RTPU_JOURNAL", raising=False)
    monkeypatch.delenv("RTPU_JOURNAL_DIR", raising=False)
    journal.shutdown()
    yield
    journal.shutdown()


def _mk(tmp_path, **kw):
    kw.setdefault("cap_mb", 1)
    kw.setdefault("flush_ms", 10)
    kw.setdefault("process_index", 0)
    return journal.Journal(directory=str(tmp_path), **kw)


def _segments(tmp_path):
    return sorted(p for p in tmp_path.iterdir() if p.suffix == ".rtj")


def _scan_all(tmp_path):
    recs = []
    for p in _segments(tmp_path):
        recs.extend(journal.scan_report(str(p))[0])
    return recs


# ---- framing + crash safety ----

def test_roundtrip_and_record_schema(tmp_path):
    j = _mk(tmp_path)
    assert j.emit("sched", {"decision": "shed"}, trace_id="tr1",
                  tenant="acme")
    assert j.flush()
    j.close()
    recs = _scan_all(tmp_path)
    # the construction-time meta record plus ours
    assert [r["k"] for r in recs] == ["meta", "sched"]
    r = recs[-1]
    assert r["d"] == {"decision": "shed"}
    assert r["t"] == "tr1" and r["n"] == "acme"
    assert r["p"] == 0 and r["s"] == 2
    assert isinstance(r["w"], float) and isinstance(r["m"], float)


def test_crc_corrupt_tail_skipped_not_fatal(tmp_path):
    j = _mk(tmp_path)
    for i in range(5):
        j.emit("instant", {"name": f"e{i}"})
    assert j.flush()
    j.close()
    path = _segments(tmp_path)[0]
    blob = bytearray(path.read_bytes())
    blob[-1] ^= 0xFF                      # flip one byte of the last payload
    path.write_bytes(bytes(blob))
    recs, report = journal.scan_report(str(path))
    # everything BEFORE the corrupt frame survives; the walk stops there
    assert len(recs) == 5                 # meta + e0..e3; e4 is the victim
    assert report["torn"] == 1
    assert report["reason"].startswith("crc@")


def test_mid_record_truncation_loses_exactly_one_record(tmp_path):
    j = _mk(tmp_path)
    for i in range(5):
        j.emit("instant", {"name": f"e{i}"})
    assert j.flush()
    j.close()
    path = _segments(tmp_path)[0]
    blob = path.read_bytes()
    last_off = list(journal.scan_segment(str(path)))[-1][1]
    path.write_bytes(blob[:-3])           # SIGKILL mid-write: torn payload
    recs, report = journal.scan_report(str(path))
    assert len(recs) == 5
    assert report["torn"] == 1
    assert report["reason"].startswith("short-payload@")
    # a truncation landing inside the frame HEADER also costs one record
    path.write_bytes(blob[:last_off + 2])
    recs, report = journal.scan_report(str(path))
    assert len(recs) == 5
    assert report["reason"] == f"short-header@{last_off}"


def test_bad_magic_yields_no_records(tmp_path):
    path = tmp_path / journal.segment_name(0, 0)
    path.write_bytes(b"NOPE" + b"x" * 64)
    recs, report = journal.scan_report(str(path))
    assert recs == [] and report["reason"] == "bad-magic"


def test_segment_rotation_under_byte_cap(tmp_path):
    # 1 MB cap -> 128 KB segments; ~1.5 MB of records must rotate AND
    # delete oldest segments to stay under the cap
    j = _mk(tmp_path, queue_cap=100_000)
    pad = "x" * 400
    for i in range(3500):
        j.emit("series", {"i": i, "pad": pad})
    assert j.flush(timeout=30)
    j.close()
    st = j.status()
    assert st["rotations"] > 0
    assert st["segments_deleted"] > 0
    assert st["total_bytes"] <= 1 << 20
    # surviving segments are the TAIL of the stream and each scans clean
    seqs = [r["seq"] for r in st["segments"]]
    assert seqs == sorted(seqs)
    recs = _scan_all(tmp_path)
    assert recs and recs[-1]["d"]["i"] == 3499
    assert all(journal.scan_report(str(p))[1]["torn"] == 0
               for p in _segments(tmp_path))


def test_restart_continues_segment_numbering(tmp_path):
    # a restarted process must never clobber its predecessor's evidence
    j1 = _mk(tmp_path)
    j1.emit("instant", {"name": "run1"})
    j1.flush()
    j1.close()
    first = [journal.parse_segment_name(p.name)[1]
             for p in _segments(tmp_path)]
    j2 = _mk(tmp_path)
    j2.emit("instant", {"name": "run2"})
    j2.flush()
    j2.close()
    second = [journal.parse_segment_name(p.name)[1]
              for p in _segments(tmp_path)]
    assert max(second) > max(first)
    assert set(first) <= set(second)      # predecessor segments intact
    names = [r["d"].get("name") for r in _scan_all(tmp_path)]
    assert "run1" in names and "run2" in names


def test_concurrent_writers_never_block_and_never_interleave(tmp_path):
    j = _mk(tmp_path, queue_cap=100_000)
    n_threads, per = 4, 500

    def worker(tid):
        for i in range(per):
            j.emit("instant", {"tid": tid, "i": i})

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert j.flush(timeout=30)
    j.close()
    recs = [r for r in _scan_all(tmp_path) if r["k"] == "instant"]
    assert len(recs) == n_threads * per
    assert j.status()["drops"] == 0
    # frames never tore each other: every record is intact and the
    # per-process sequence is exactly 1..N+1 (meta took seq 1)
    seqs = sorted(r["s"] for r in recs)
    assert seqs == list(range(2, n_threads * per + 2))
    by_tid = {}
    for r in recs:
        by_tid.setdefault(r["d"]["tid"], []).append(r["d"]["i"])
    assert all(sorted(v) == list(range(per)) for v in by_tid.values())


def test_full_queue_drops_and_counts_never_blocks(tmp_path):
    j = _mk(tmp_path, queue_cap=4, flush_ms=50)
    # a burst far faster than the 50 ms drain interval: the queue caps
    # at 4, everything else drops-and-counts without blocking
    sent = [j.emit("instant", {"i": i}) for i in range(100)]
    assert j.flush(timeout=10)
    # one record AFTER the drain makes the sequence hole visible on disk
    assert j.emit("instant", {"i": "after"})
    assert j.flush(timeout=10)
    j.close()
    drops = j.status()["drops"]
    assert drops >= 50 and sent.count(False) == drops
    recs = [r for r in _scan_all(tmp_path) if r["k"] == "instant"]
    # dropped records leave sequence gaps — the on-disk drop evidence
    gaps = postmortem.seq_gaps(recs)
    assert sum(g["missing"] for g in gaps) == drops


# ---- zero overhead off + env surface ----

def test_disabled_is_a_single_env_check(monkeypatch):
    assert not journal.enabled()
    journal.emit("instant", {"name": "x"})
    journal.emit_event({"ph": "X", "name": "x"})
    assert journal._SINGLETON is None       # no instance, thread, or file
    assert journal.status_block() == {"enabled": False}
    assert journal.journalz()["enabled"] is False
    monkeypatch.setenv("RTPU_JOURNAL", "0")
    monkeypatch.setenv("RTPU_JOURNAL_DIR", "/nonexistent")
    assert not journal.enabled()            # explicit 0 beats DIR-implies-on


def test_dir_implies_enabled(monkeypatch, tmp_path):
    monkeypatch.setenv("RTPU_JOURNAL_DIR", str(tmp_path))
    assert journal.enabled()
    journal.emit("instant", {"name": "x"})
    j = journal.get()
    assert j is not None and j.flush()
    z = journal.journalz()
    assert z["enabled"] and z["records_written"] >= 2
    assert z["dir"] == str(tmp_path)
    blk = journal.status_block()
    assert blk["enabled"] and blk["segments"] >= 1
    assert set(blk) >= {"dir", "total_bytes", "records_written", "drops",
                        "flush_lag_seconds", "queue_depth"}


def test_unwritable_dir_fails_open(monkeypatch, tmp_path):
    deny = tmp_path / "file-not-dir"
    deny.write_text("occupied")
    monkeypatch.setenv("RTPU_JOURNAL_DIR", str(deny))
    journal.emit("instant", {"name": "x"})  # must not raise
    assert journal.get() is None
    assert journal.journalz()["failed"] is True


# ---- exit consolidation + federation ----

def test_exitdump_owns_the_journal_close(monkeypatch, tmp_path):
    monkeypatch.setenv("RTPU_JOURNAL_DIR", str(tmp_path))
    journal.emit("instant", {"name": "pre-exit"})
    j = journal.get()
    assert "journal" in exitdump.registered()
    exitdump.run_all()                      # the SIGTERM/atexit path
    assert j._closed
    names = [r["d"].get("name") for r in _scan_all(tmp_path)]
    assert "pre-exit" in names              # drained + fsynced by close
    exitdump.run_all()                      # idempotent


def test_clusterz_merges_member_journals():
    merged = cl._merge_journal({
        "process_0": {"reachable": True, "journal": {
            "enabled": True, "dir": "/a", "segments": 2,
            "total_bytes": 1000, "drops": 3, "flush_lag_seconds": 0.5}},
        "process_1": {"reachable": True, "journal": {
            "enabled": True, "dir": "/b", "segments": 1,
            "total_bytes": 500, "drops": 0, "flush_lag_seconds": 1.25}},
        "process_2": {"reachable": True, "journal": {"enabled": False}},
        "process_3": {"reachable": False},
    })
    assert merged["processes_enabled"] == 2
    assert merged["bytes_total"] == 1500
    assert merged["drops_total"] == 3
    assert merged["worst_flush_lag_seconds"] == 1.25
    assert merged["by_process"]["process_0"]["bytes"] == 1000
    assert merged["by_process"]["process_2"] == {"enabled": False}
    assert "process_3" not in merged["by_process"]


# ---- postmortem replay ----

def _synthetic_run(tmp_path, name, scale=1.0):
    d = tmp_path / name
    d.mkdir()
    j = journal.Journal(directory=str(d), cap_mb=1, flush_ms=10,
                        process_index=0)
    for i in range(3):
        j.emit("span", {"ph": "X", "name": "sweep.hop", "sid": 10 + i,
                        "parent": 1, "dur": 1000.0 * scale, "tid": 7},
               trace_id="tr-final")
    j.emit("span", {"ph": "X", "name": "sweep", "sid": 1, "parent": None,
                    "dur": 5000.0 * scale, "tid": 7}, trace_id="tr-final")
    j.emit("ledger", {"algorithm": "PageRank", "job_id": "q1",
                      "status": "done",
                      "phase_seconds": {"build": 0.01 * scale,
                                        "fold": 0.02 * scale}},
           trace_id="tr-final", tenant="acme")
    j.emit("epoch", {"job_id": "live1", "algorithm": "DegreeBasic",
                     "result_time": 42, "delta_rows": 5}, trace_id="tr-e")
    j.emit("breaker", {"peer": "process_1", "state": "down",
                       "failures": 2})
    assert j.flush()
    j.close()
    return d


def test_postmortem_timeline_filters_and_merge(tmp_path):
    d = _synthetic_run(tmp_path, "run")
    segs = postmortem.load_segments([str(d)])
    recs = postmortem.merge_records(segs)
    walls = [r["w"] for r in recs]
    assert walls == sorted(walls)
    st = postmortem.status(segs)
    p0 = st["processes"]["process_0"]
    assert p0["records"] == len(recs) and p0["torn_segments"] == 0
    assert p0["kinds"]["span"] == 4 and p0["kinds"]["ledger"] == 1
    by_trace = postmortem.timeline(recs, trace="tr-final")
    assert {r["k"] for r in by_trace} == {"span", "ledger"}
    by_tenant = postmortem.timeline(recs, tenant="acme")
    assert [r["k"] for r in by_tenant] == ["ledger"]
    tail = postmortem.timeline(recs, limit=2)
    assert tail == recs[-2:]                # the tail, not the head
    assert postmortem.timeline(recs, kind="breaker",
                               since=walls[0], until=walls[-1])


def test_postmortem_reconstructs_final_state(tmp_path):
    d = _synthetic_run(tmp_path, "run")
    recs = postmortem.merge_records(postmortem.load_segments([str(d)]))
    out = postmortem.reconstruct(recs, process=0)
    assert out["last_record"]["kind"] == "breaker"
    assert out["meta"]["version"] == 1
    assert out["final_trace"]["trace_id"] == "tr-final"
    assert [e["name"] for e in out["final_trace"]["events"]] \
        == ["sweep.hop"] * 3 + ["sweep"]
    assert out["last_epoch_by_job"]["live1"]["result_time"] == 42
    assert out["last_ledgers"][-1]["algorithm"] == "PageRank"
    assert "down" in out["last_breaker"][-1]["summary"]
    missing = postmortem.reconstruct(recs, process=9)
    assert "error" in missing


def test_postmortem_exports_chrome_and_collapsed(tmp_path):
    d = _synthetic_run(tmp_path, "run")
    recs = postmortem.merge_records(postmortem.load_segments([str(d)]))
    doc = postmortem.chrome_trace(recs)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 4
    # spans journal at COMPLETION: re-based start = wall*1e6 - dur
    for e, r in zip(spans, [x for x in recs if x["k"] == "span"]):
        assert e["ts"] == pytest.approx(r["w"] * 1e6 - e["dur"])
        assert e["pid"] == 0
    stacks = postmortem.collapsed_stacks(recs)
    # parent chains with self-time weights: the root's bar excludes its
    # children (5000 - 3*1000), each child line carries its own 1000
    assert stacks["process_0;sweep"] == 2000
    assert stacks["process_0;sweep;sweep.hop"] == 3000


def test_postmortem_diff_attributes_regressions(tmp_path):
    a = _synthetic_run(tmp_path, "a", scale=1.0)
    b = _synthetic_run(tmp_path, "b", scale=2.0)
    ra = postmortem.merge_records(postmortem.load_segments([str(a)]))
    rb = postmortem.merge_records(postmortem.load_segments([str(b)]))
    out = postmortem.diff(ra, rb, threshold=0.25)
    assert not out["ok"]
    assert "phase_seconds:PageRank/fold" in out["regressions"]
    assert "span_seconds:sweep" in out["regressions"]
    m = out["metrics"]["phase_seconds:PageRank/build"]
    assert m["delta_rel"] == pytest.approx(1.0)
    # same run against itself: clean
    assert postmortem.diff(ra, ra)["ok"]


def test_postmortem_cli_subcommands(tmp_path, capsys):
    d = _synthetic_run(tmp_path, "run")
    assert postmortem.main(["status", str(d)]) == 0
    st = json.loads(capsys.readouterr().out)
    assert st["processes"]["process_0"]["records"] > 0
    assert postmortem.main(["timeline", str(d), "--kind", "ledger",
                            "--format", "json"]) == 0
    rows = json.loads(capsys.readouterr().out)
    assert len(rows) == 1 and rows[0]["d"]["algorithm"] == "PageRank"
    assert postmortem.main(["reconstruct", str(d), "--process", "0"]) == 0
    capsys.readouterr()
    out_file = tmp_path / "trace.json"
    assert postmortem.main(["export", str(d), "--format", "chrome",
                            "--out", str(out_file)]) == 0
    assert json.loads(out_file.read_text())["traceEvents"]
    b = _synthetic_run(tmp_path, "b", scale=2.0)
    assert postmortem.main(["diff", str(d), str(b)]) == 1   # regressed
    assert postmortem.main(["diff", str(d), str(d)]) == 0   # self-clean
    capsys.readouterr()
    assert postmortem.main(["status", str(tmp_path / "empty")]) == 2


# ---- end to end: a real job's evidence reaches disk ----

def test_job_evidence_survives_to_disk(monkeypatch, tmp_path):
    from raphtory_tpu.jobs import registry
    from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery

    monkeypatch.setenv("RTPU_JOURNAL_DIR", str(tmp_path))
    monkeypatch.setenv("RTPU_JOURNAL_FLUSH_MS", "10")
    was = TRACER.enabled
    TRACER.enable()
    try:
        pipe = IngestionPipeline()
        rng = np.random.default_rng(0)
        pipe.add_source(IterableSource(
            [EdgeAdd(int(t), int(a), int(b))
             for t, a, b in zip(np.sort(rng.integers(0, 100, 200)),
                                rng.integers(0, 30, 200),
                                rng.integers(0, 30, 200))], name="s"))
        pipe.run()
        g = TemporalGraph(pipe.log, pipe.watermarks)
        mgr = AnalysisManager(g)
        job = mgr.submit(registry.resolve("ConnectedComponents"),
                         ViewQuery(90))
        assert job.wait(60) and job.status == "done"
        j = journal.get()
        assert j is not None and j.flush(timeout=10)
    finally:
        TRACER.enabled = was
    recs = postmortem.merge_records(
        postmortem.load_segments([str(tmp_path)]))
    ledgers = [r for r in recs if r["k"] == "ledger"]
    assert ledgers and any(
        (r["d"] or {}).get("algorithm") == "ConnectedComponents"
        for r in ledgers)
    assert ledgers[-1]["t"]                 # stamped with the trace id
    assert any(r["k"] == "span" for r in recs)
    # the same evidence is what the REST plane reports at /journalz
    z = journal.journalz()
    assert z["records_written"] == len(recs)
