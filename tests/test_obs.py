"""Observability: metrics wiring + scrape server (L8)."""

import urllib.request

from prometheus_client import generate_latest

from raphtory_tpu.algorithms import DegreeBasic
from raphtory_tpu.core.service import TemporalGraph
from raphtory_tpu.ingestion.pipeline import IngestionPipeline
from raphtory_tpu.ingestion.source import RandomSource
from raphtory_tpu.jobs.manager import AnalysisManager, ViewQuery
from raphtory_tpu.obs import METRICS, MetricsServer


def _value(metric, labels=()):
    m = metric.labels(*labels) if labels else metric
    return m._value.get()


def test_pipeline_and_job_metrics_flow():
    before = _value(METRICS.views_computed)
    pipe = IngestionPipeline()
    pipe.add_source(RandomSource(3_000, id_pool=200, seed=2, name="m1"))
    pipe.run()
    assert _value(METRICS.events_ingested, ("m1",)) == 3_000
    g = TemporalGraph(pipe.log, pipe.watermarks)
    mgr = AnalysisManager(g)
    job = mgr.submit(DegreeBasic(), ViewQuery(g.latest_time))
    assert job.wait(120) and job.status == "done", job.error
    assert _value(METRICS.views_computed) == before + 1
    assert _value(METRICS.jobs_completed, ("done",)) >= 1
    # text exposition contains our families + the RSS gauge
    text = generate_latest(METRICS.registry).decode()
    assert "raphtory_events_ingested_total" in text
    assert "raphtory_host_rss_bytes" in text
    rss = [ln for ln in text.splitlines()
           if ln.startswith("raphtory_host_rss_bytes")][0]
    assert float(rss.split()[-1]) > 1e6  # an RSS below 1MB would be a bug


def test_parse_error_counter():
    class Boom:
        name = "boom"
        disorder = 0

        def __iter__(self):
            yield "x"
            raise RuntimeError("source died")

    pipe = IngestionPipeline()
    pipe.add_source(Boom())
    pipe.run()
    assert "boom" in pipe.errors
    assert _value(METRICS.parse_errors, ("boom",)) == 1
    # a dead source releases the fence rather than wedging it
    assert pipe.watermarks.safe_time() == 2**62


def test_metrics_server_scrape():
    srv = MetricsServer(port=0)  # ephemeral port
    srv.start()
    try:
        port = srv._server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "raphtory_log_events" in body
    finally:
        srv.stop()


def test_metrics_server_repeated_start_stop_leaks_no_threads():
    import threading

    for _ in range(3):
        srv = MetricsServer(port=0)
        srv.start()
        t = srv._thread
        assert t is not None and t.is_alive()
        srv.stop()
        # stop() joins the scrape thread and drops the handle, so
        # repeated start/stop cycles cannot accumulate live threads
        assert srv._thread is None and srv._server is None
        assert not t.is_alive()
        assert t not in threading.enumerate()


def test_records_dropped_counter():
    from raphtory_tpu.ingestion.source import IterableSource
    from raphtory_tpu.examples import RandomJsonParser

    pipe = IngestionPipeline()
    pipe.add_source(IterableSource(
        ['{"VertexAdd":{"messageID":1,"srcID":2}}', "not json", "{}"],
        name="drop1"), RandomJsonParser())
    pipe.run()
    assert not pipe.errors
    assert _value(METRICS.records_dropped, ("drop1",)) == 2
    assert _value(METRICS.events_ingested, ("drop1",)) == 1


def test_supersteps_counted_once_per_batched_run():
    from raphtory_tpu.core.service import TemporalGraph as TG
    from raphtory_tpu.ingestion.source import RandomSource as RS
    from raphtory_tpu.jobs.manager import AnalysisManager as AM, ViewQuery as VQ
    from raphtory_tpu.algorithms import ConnectedComponents

    pipe = IngestionPipeline()
    pipe.add_source(RS(2_000, id_pool=100, seed=6, name="ss"))
    pipe.run()
    g = TG(pipe.log, pipe.watermarks)
    before = _value(METRICS.supersteps)
    job = AM(g).submit(ConnectedComponents(),
                       VQ(g.latest_time, windows=(10_000, 1_000, 100)))
    assert job.wait(120) and job.status == "done", job.error
    steps = job.results[0]["steps"]
    # three windows, ONE device run: counter advanced by steps, not 3*steps
    assert _value(METRICS.supersteps) == before + steps
