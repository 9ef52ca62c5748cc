"""Ingestion pipeline, parsers, watermark fence."""

import numpy as np
import pytest

from raphtory_tpu.core.service import StaleViewError, TemporalGraph
from raphtory_tpu.ingestion.parser import (
    CsvEdgeListParser,
    GabParser,
    JsonUpdateParser,
)
from raphtory_tpu.ingestion.pipeline import IngestionPipeline
from raphtory_tpu.ingestion.source import (
    FileSource,
    IterableSource,
    RandomSource,
    RateLimited,
)
from raphtory_tpu.ingestion.updates import EdgeAdd, VertexDelete, assign_id


def test_csv_parser_pipeline(tmp_path):
    p = tmp_path / "edges.csv"
    p.write_text("a,b,1\nb,c,2\na,c,3\n")
    pipe = IngestionPipeline()
    pipe.add_source(FileSource(str(p)), CsvEdgeListParser())
    pipe.run()
    assert pipe.counts[str(p)] == 3
    g = TemporalGraph(pipe.log, pipe.watermarks)
    v = g.view_at(3)
    assert v.n_active == 3 and v.m_active == 3
    # string ids resolved through assign_id
    li = v.local_index([assign_id("a")])
    assert li[0] >= 0
    assert v.out_deg[li[0]] == 2


def test_gab_parser():
    # deprecated alias of examples.gab.GabUserGraphParser: typed endpoint
    # vertices + the reply edge; raw epoch timestamps pass through
    par = GabParser()
    rows = par("1470000000;x;101;y;z;202")
    assert rows[-1] == EdgeAdd(time=1470000000, src=101, dst=202)
    assert len(rows) == 3
    assert par("garbage;;row") == []
    assert par("1470000000;x;101;y;z;-7") == []  # non-positive parent drop


def test_json_parser():
    par = JsonUpdateParser()
    u = par('{"type": "edgeAdd", "t": 5, "src": 1, "dst": 2}')
    assert u == [EdgeAdd(5, 1, 2)]
    u = par('{"type": "vertexDelete", "t": 9, "id": 4}')
    assert u == [VertexDelete(9, 4)]
    with pytest.raises(ValueError):
        par('{"type": "nope", "t": 1}')


def test_random_source_runs_and_counts():
    pipe = IngestionPipeline()
    pipe.add_source(RandomSource(5_000, id_pool=500, seed=1))
    pipe.run()
    assert pipe.log.n == 5_000
    g = TemporalGraph(pipe.log, pipe.watermarks)
    v = g.view_at(g.latest_time)
    assert v.n_active > 0


def test_watermark_fence_blocks_until_source_passes():
    pipe = IngestionPipeline(batch_size=10)
    g = TemporalGraph(pipe.log, pipe.watermarks)
    src = IterableSource([EdgeAdd(t, 1, 2) for t in range(100)], name="s")
    pipe.add_source(src)
    # nothing ingested yet: view at 50 must refuse
    with pytest.raises(StaleViewError):
        g.view_at(50)
    pipe.run()
    v = g.view_at(50)  # source finished -> fence open
    assert v.m_active == 1


def test_watermark_disorder_bound():
    pipe = IngestionPipeline(batch_size=4)
    g = TemporalGraph(pipe.log, pipe.watermarks)

    def gen():
        for t in range(0, 100):
            yield EdgeAdd(t, t, t + 1)

    src = IterableSource(gen(), name="s", disorder=20)
    pipe.add_source(src)
    pipe.start()
    pipe.join()
    # finished -> safe regardless of disorder
    assert g.safe_time() >= 99
    assert pipe.log.n == 100


def test_live_threaded_ingestion_with_fence():
    import threading

    gate = threading.Event()

    def slow():
        for t in range(0, 200):
            if t == 100:
                gate.wait(5)
            yield EdgeAdd(t, t % 10, (t + 1) % 10)

    pipe = IngestionPipeline(batch_size=8)
    g = TemporalGraph(pipe.log, pipe.watermarks)
    pipe.add_source(IterableSource(slow(), name="slow"))
    pipe.start()
    # watermark advances past some prefix but not to the end
    import time
    deadline = time.monotonic() + 5
    while g.safe_time() < 50 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert 50 <= g.safe_time() < 2**62
    with pytest.raises(StaleViewError):
        g.view_at(10**9)
    gate.set()
    pipe.join(5)
    assert g.safe_time() >= 199
    v = g.view_at(199)
    assert v.n_active == 10


def test_view_cache_reuse_and_invalidation():
    pipe = IngestionPipeline()
    g = TemporalGraph(pipe.log, pipe.watermarks)
    pipe.add_source(IterableSource([EdgeAdd(1, 1, 2)], name="a"))
    pipe.run()
    v1 = g.view_at(1)
    assert g.view_at(1) is v1  # cache hit
    g.log.add_edge(2, 2, 3)   # append invalidates (version bump)
    v2 = g.view_at(1)
    assert v2 is not v1


def test_rate_limited_wrapper():
    import time

    src = RateLimited(
        IterableSource([EdgeAdd(t, 1, 2) for t in range(50)], name="x"),
        rate=1000.0)
    t0 = time.monotonic()
    items = list(src)
    assert len(items) == 50
    assert time.monotonic() - t0 >= 0.04  # ~50/1000s floor


def test_assign_id_stability():
    a1 = assign_id("alice")
    assert a1 == assign_id("alice")
    assert a1 != assign_id("bob")
    assert assign_id(42) == 42


def test_watermark_wait_for_wakes_on_advance():
    """The fence wait is event-driven: a waiter parked on wait_for(T) wakes
    as soon as the watermark crosses T — far faster than a polling loop —
    and times out cleanly when it never does."""
    import threading
    import time as _t

    from raphtory_tpu.ingestion.watermark import WatermarkRegistry

    wm = WatermarkRegistry()
    wm.register("s")
    assert not wm.wait_for(100, timeout=0.05)  # times out, fence not crossed

    woke = {}

    def waiter():
        t0 = _t.perf_counter()
        ok = wm.wait_for(100, timeout=5.0)
        woke["ok"] = ok
        woke["latency"] = _t.perf_counter() - t0

    th = threading.Thread(target=waiter)
    th.start()
    _t.sleep(0.1)
    t_adv = _t.perf_counter()
    wm.advance("s", 150)
    th.join(2.0)
    assert woke["ok"]
    # woke promptly after advance (well before the 5 s timeout would fire);
    # no lower bound — a slow-to-schedule waiter may observe the fence
    # already crossed, which is also correct
    assert _t.perf_counter() - t_adv < 0.5
    # finish() also releases waiters (safe_time -> +inf)
    wm2 = WatermarkRegistry()
    wm2.register("x")
    th2 = threading.Thread(target=lambda: wm2.wait_for(10**9, timeout=5.0))
    th2.start()
    wm2.finish("x")
    th2.join(1.0)
    assert not th2.is_alive()


def test_staged_pipeline_matches_direct_mode():
    """queue_max_events>0 routes parse → bounded queue → writer thread;
    the resulting log equals direct mode's and the backlog drains to 0."""
    def updates():
        rng = np.random.default_rng(3)
        return [EdgeAdd(int(t), int(a), int(b))
                for t, a, b in zip(np.sort(rng.integers(0, 500, 3000)),
                                   rng.integers(0, 50, 3000),
                                   rng.integers(0, 50, 3000))]

    direct = IngestionPipeline(batch_size=128)
    direct.add_source(IterableSource(updates(), name="s"))
    direct.run()

    staged = IngestionPipeline(batch_size=128, queue_max_events=512)
    staged.add_source(IterableSource(updates(), name="s"))
    staged.run()

    assert not staged.errors and not direct.errors
    assert staged.backlog() == 0
    assert staged.log.n == direct.log.n == 3000
    for col in ("time", "kind", "src", "dst"):
        np.testing.assert_array_equal(staged.log.column(col),
                                      direct.log.column(col))
    # both fences fully released
    assert staged.watermarks.safe_time() == direct.watermarks.safe_time()


def test_staged_watermark_never_overtakes_queue():
    """safe_time must lag events still sitting in the queue: the advance
    rides the batch through the writer, so a view at the watermark always
    sees every event the fence promises."""
    import threading
    import time as _t

    gate = threading.Event()
    n = 600

    class GatedIterable:
        def __iter__(self):
            for i in range(n):
                if i == 300:
                    gate.wait(10)   # stall mid-stream with queue part-full
                yield EdgeAdd(i, i % 20, (i + 1) % 20)

    pipe = IngestionPipeline(batch_size=64, queue_max_events=100_000)
    src = IterableSource(GatedIterable(), name="gated")
    pipe.add_source(src)

    # slow the writer so batches pile up in the queue
    orig_append = pipe.log.append_batch

    def slow_append(*a, **k):
        _t.sleep(0.02)
        return orig_append(*a, **k)

    pipe.log.append_batch = slow_append
    pipe.start()
    deadline = _t.monotonic() + 10
    while pipe.backlog() == 0 and _t.monotonic() < deadline:
        _t.sleep(0.005)
    # invariant while the queue is non-empty: every event <= safe_time is
    # already IN the log (count events in log with time <= w)
    for _ in range(50):
        w = pipe.watermarks.safe_time()
        n_log = pipe.log.n
        if w >= 0 and w < 2**62:
            times = pipe.log.column("time")[:n_log]
            assert (times <= w).sum() == (w + 1), (w, n_log)
        _t.sleep(0.002)
    gate.set()
    pipe.join(20)
    assert pipe.backlog() == 0 and pipe.log.n == n


def test_staged_writer_failure_poisons_source():
    """An append failure in the staged writer stops the source (no events
    land past the hole), surfaces the ROOT cause, and still releases the
    watermark fence — matching direct mode's failure semantics."""
    boom = {"armed": False}

    pipe = IngestionPipeline(batch_size=32, queue_max_events=4096)
    orig_append = pipe.log.append_batch

    def flaky_append(*a, **k):
        if boom["armed"]:
            raise MemoryError("injected append failure")
        return orig_append(*a, **k)

    pipe.log.append_batch = flaky_append

    def stream():
        for i in range(2000):
            if i == 500:
                boom["armed"] = True
            yield EdgeAdd(i, i % 20, (i + 1) % 20)

    pipe.add_source(IterableSource(stream(), name="s"))
    pipe.run()
    assert "MemoryError" in pipe.errors["s"]          # root cause, not the
    assert "injected append failure" in pipe.errors["s"]  # poison marker
    assert pipe.log.n <= 512                           # nothing past the hole
    assert pipe.watermarks.safe_time() >= 2**62        # fence released
    assert pipe.backlog() == 0 or pipe._q_done


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
