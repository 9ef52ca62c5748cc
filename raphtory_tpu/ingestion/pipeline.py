"""The ingestion pipeline: sources → parsers → event log (+ watermarks).

Replaces the reference's Spout → RouterManager(10 workers) → Writer(10
IngestionWorkers) actor pipeline (SURVEY §3.1). Stages are host threads
feeding the shared append-only ``EventLog`` in batches; the partition/sync
machinery has no analogue because the log is global and snapshots immutable.
Batched appends keep the hot path vectorised (one lock acquisition and one
memcpy per batch, not per update — the reference pays an actor hop per
update).
"""

from __future__ import annotations

import threading

import numpy as np

from ..core import events as ev
from ..core.events import EventLog
from ..obs import freshness as _fresh
from ..obs.metrics import METRICS
from ..obs.trace import TRACER
from .parser import IdentityParser, Parser
from .source import Source
from .updates import EdgeAdd, EdgeDelete, VertexAdd, VertexDelete, assign_id
from .watermark import WatermarkRegistry


class IngestionPipeline:
    def __init__(self, log: EventLog | None = None,
                 watermarks: WatermarkRegistry | None = None,
                 batch_size: int = 4096, queue_max_events: int = 0):
        if log is not None and not hasattr(log, "append_batch"):
            # catch TemporalGraph-for-EventLog misuse at construction —
            # otherwise it surfaces as an AttributeError inside a consumer
            # thread, long after the mistake
            raise TypeError(
                f"log must be an EventLog (got {type(log).__name__}); "
                "pass graph.log, not the graph")
        self.log = log if log is not None else EventLog()
        self.watermarks = watermarks if watermarks is not None else WatermarkRegistry()
        self.batch_size = batch_size
        self._threads: list[threading.Thread] = []
        self._stop = threading.Event()
        # sources added and not yet handed to run()/start()
        self._feeds: list[tuple[Source, Parser]] = []
        self.counts: dict[str, int] = {}
        self.errors: dict[str, str] = {}
        # source threads AND the staged writer all record failures here —
        # one lock keeps the first-root-cause-wins setdefault honest
        # (rtpulint RT010: no common lock across those writers otherwise)
        self._err_lock = threading.Lock()
        # staged mode (queue_max_events > 0): parse and append run in
        # separate threads with a BOUNDED event queue between them — the
        # reference's writer-mailbox shape (SURVEY §4.5: queue depth was
        # the paper's saturation oracle, WriterLogger.scala:21-30). A full
        # queue blocks the source (backpressure), so memory stays bounded
        # and a pinned-at-max backlog gauge IS the saturation signal.
        self.queue_max_events = queue_max_events
        self._q: list = []
        self._q_events = 0
        self._q_cv = threading.Condition()
        self._q_done = False
        self._writer: threading.Thread | None = None
        self._failed: set[str] = set()   # sources whose writer append died
        # freshness plane (obs/freshness.py): weakly attached so /freshz
        # and the /slz series ring can read this pipeline's staged
        # backlog + queue bound without pinning it
        _fresh.FRESH.attach_pipeline(self)

    @property
    def staged(self) -> bool:
        return self.queue_max_events > 0

    def backlog(self) -> int:
        """Parsed-but-unappended event count (0 in direct mode)."""
        with self._q_cv:
            return self._q_events

    def add_source(self, source: Source, parser: Parser | None = None) -> None:
        if source.name in self.counts:
            raise ValueError(
                f"duplicate source name {source.name!r}: watermarks are keyed "
                f"by name; give each source a unique name")
        parser = parser if parser is not None else IdentityParser()
        self._feeds.append((source, parser))
        self.watermarks.register(source.name)
        # the declared disorder bound rides into the freshness plane so
        # the out-of-order histogram can be judged against it (an
        # observed distance PAST the bound is a watermark-promise risk
        # the out-of-order-excess advisor rule alarms on)
        _fresh.FRESH.register_source(source.name,
                                     disorder=source.disorder)
        self.counts[source.name] = 0

    # ---- synchronous mode (tests, file replay, benchmarks) ----

    def _take_feeds(self) -> list[tuple[Source, Parser]]:
        """The feeds no earlier ``run``/``start`` consumed: a source added
        to a running node joins without replaying the drained ones."""
        feeds, self._feeds = self._feeds, []
        return feeds

    def run(self) -> None:
        """Drain every not-yet-consumed source to exhaustion on the
        calling thread."""
        self._ensure_writer()
        for source, parser in self._take_feeds():
            self._consume(source, parser)
        self._finish_writer()

    # ---- live mode (threads; SpoutTrait self-scheduling analogue) ----

    def start(self) -> None:
        """One consumer thread per not-yet-consumed source; call again
        after ``add_source`` to start the late joiner alone."""
        self._ensure_writer()
        for source, parser in self._take_feeds():
            t = threading.Thread(
                target=self._consume, args=(source, parser),
                name=f"ingest-{source.name}", daemon=True)
            self._threads.append(t)
            t.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout)
        self._threads.clear()
        self._finish_writer(timeout)

    def join(self, timeout: float | None = None) -> None:
        for t in self._threads:
            t.join(timeout)
        self._finish_writer(timeout)

    # ---- staged-mode writer (bounded mailbox between parse and append) ----

    def _ensure_writer(self) -> None:
        if not self.staged or (self._writer is not None
                               and self._writer.is_alive()):
            return
        self._q_done = False
        self._writer = threading.Thread(
            target=self._writer_loop, name="ingest-writer", daemon=True)
        self._writer.start()

    def _finish_writer(self, timeout: float | None = None) -> None:
        if self._writer is None:
            return
        with self._q_cv:
            self._q_done = True
            self._q_cv.notify_all()
        self._writer.join(timeout)
        if not self._writer.is_alive():   # a timed-out join keeps the ref,
            self._writer = None           # so no second writer can spawn

    def _writer_loop(self) -> None:
        while True:
            with self._q_cv:
                while not self._q and not self._q_done:
                    self._q_cv.wait(0.1)
                if not self._q:
                    return
                kind, name, payload, wm = self._q.pop(0)
                if kind == "batch":
                    self._q_events -= len(payload[0])
                    METRICS.ingest_backlog.set(self._q_events)
                    self._q_cv.notify_all()   # unblock backpressured sources
            try:
                if kind == "batch":
                    if name in self._failed:
                        continue   # poisoned: no appends, no wm advance
                    t, k, s, d, props = payload
                    if len(t):
                        with TRACER.span("ingest.append", source=name,
                                         events=int(len(t)), stage="writer"):
                            self.log.append_batch(t, k, s, d, props=props)
                        METRICS.log_events.set(self.log.n)
                    if wm is not None:
                        self.watermarks.advance(name, wm)
                else:   # "finish": released only once the source's batches
                    self.watermarks.finish(name)   # all landed (FIFO)
            except Exception as e:  # noqa: BLE001 — surfaced via errors
                import traceback

                # poison the source: later batches must not land past the
                # hole (the fence would claim completeness over missing
                # events) — matching direct mode, where the exception kills
                # the consume loop. The "finish" marker still releases the
                # fence, exactly like _consume's finally.
                # record the ROOT cause BEFORE raising the poison flag: a
                # source seeing _failed re-raises a generic RuntimeError,
                # and its setdefault must lose to this one, not win a race
                with self._err_lock:
                    self.errors.setdefault(name, (
                        f"{type(e).__name__}: {e}\n"
                        f"{traceback.format_exc()}"))
                    self._failed.add(name)

    def _sink_batch(self, name: str, t, k, s, d, props=None,
                    wm: int | None = None) -> None:
        """Deliver one parsed batch to the log: directly (default), or via
        the bounded queue (staged). The watermark advance rides WITH the
        batch so safe_time never overtakes events still in the queue."""
        # freshness stamp at ARRIVAL, before any queueing: op mix,
        # out-of-orderness vs the source high water, and the pending
        # queryable record — staged-queue wait is part of
        # ingest-to-queryable by design (obs/freshness.py; never raises)
        if len(t):
            _fresh.FRESH.note_batch(
                name, t, k, stage="staged" if self.staged else "direct")
        if not self.staged:
            if len(t):
                with TRACER.span("ingest.append", source=name,
                                 events=int(len(t)), stage="direct"):
                    self.log.append_batch(t, k, s, d, props=props)
                METRICS.log_events.set(self.log.n)
            if wm is not None:
                self.watermarks.advance(name, wm)
            return
        if name in self._failed:
            # mirror direct mode, where the append exception killed this
            # source's consume loop: re-raise the writer's failure into it
            raise RuntimeError(f"ingest writer failed for source {name!r} "
                               f"(see pipeline.errors)")
        with self._q_cv:
            while (not self._q_done
                   and self._q_events + len(t) > self.queue_max_events
                   and self._q_events > 0 and not self._stop.is_set()):
                self._q_cv.wait(0.1)   # backpressure: block, don't grow
            if self._q_done:
                # writer retired (post-stop zombie source, or it retired
                # WHILE we were blocked above): drop rather than strand
                # events on a queue nothing will ever drain
                return
            self._q.append(("batch", name, (t, k, s, d, props), wm))
            self._q_events += len(t)
            METRICS.ingest_backlog.set(self._q_events)
            self._q_cv.notify_all()

    def _sink_finish(self, name: str) -> None:
        if not self.staged:
            self.watermarks.finish(name)
            return
        with self._q_cv:
            if self._q_done:   # writer retired: release the fence directly
                self.watermarks.finish(name)
                return
            self._q.append(("finish", name, None, None))
            self._q_cv.notify_all()

    # ---- internals ----

    def _consume(self, source: Source, parser: Parser) -> None:
        try:
            with TRACER.span("ingest.source", source=source.name):
                self._consume_inner(source, parser)
        except Exception as e:  # noqa: BLE001 — surfaced via self.errors
            import traceback

            # setdefault: if the staged writer already recorded the root
            # cause, the re-raised poison marker must not mask it
            with self._err_lock:
                self.errors.setdefault(source.name, (
                    f"{type(e).__name__}: {e}\n{traceback.format_exc()}"))
            METRICS.parse_errors.labels(source.name).inc()
        finally:
            # A dead source will never append again — releasing the fence is
            # correct AND required, or one bad line would wedge safe_time()
            # forever while the failure sat invisible in a daemon thread.
            # (Staged: the release queues BEHIND the source's last batch.)
            self._sink_finish(source.name)

    def _consume_inner(self, source: Source, parser: Parser) -> None:
        if self._consume_bulk(source, parser):
            return
        if self._consume_columnar(source, parser):
            return
        bt, bk, bs, bd = [], [], [], []
        pending_props: list[tuple[int, dict]] = []  # (batch offset, props)
        max_t = -(2**62)
        n = 0

        def flush(wm: int | None = None):
            nonlocal bt, bk, bs, bd, pending_props
            if not bt and wm is None:
                return
            METRICS.events_ingested.labels(source.name).inc(len(bt))
            self._sink_batch(
                source.name,
                np.asarray(bt, np.int64), np.asarray(bk, np.uint8),
                np.asarray(bs, np.int64), np.asarray(bd, np.int64),
                props=pending_props or None, wm=wm)
            bt, bk, bs, bd, pending_props = [], [], [], [], []

        dropped_ctr = METRICS.records_dropped.labels(source.name)
        for raw in source:
            if self._stop.is_set():
                break
            updates = parser(raw)
            if not updates:  # malformed-or-filtered: visible, not fatal
                dropped_ctr.inc()
            for u in updates:
                off = len(bt)
                if isinstance(u, EdgeAdd):
                    bt.append(u.time); bk.append(ev.EDGE_ADD)
                    bs.append(assign_id(u.src)); bd.append(assign_id(u.dst))
                    if u.props:
                        pending_props.append((off, u.props))
                elif isinstance(u, VertexAdd):
                    bt.append(u.time); bk.append(ev.VERTEX_ADD)
                    bs.append(assign_id(u.vid)); bd.append(-1)
                    if u.props:
                        pending_props.append((off, u.props))
                elif isinstance(u, EdgeDelete):
                    bt.append(u.time); bk.append(ev.EDGE_DELETE)
                    bs.append(assign_id(u.src)); bd.append(assign_id(u.dst))
                elif isinstance(u, VertexDelete):
                    bt.append(u.time); bk.append(ev.VERTEX_DELETE)
                    bs.append(assign_id(u.vid)); bd.append(-1)
                else:
                    raise TypeError(f"parser produced non-update {u!r}")
                max_t = max(max_t, u.time)
                n += 1
            if len(bt) >= self.batch_size:
                # -1: a later tuple may still arrive at exactly
                # max_t - disorder (equal timestamps are legal), so the
                # promise "no event <= w will ever be appended" needs the
                # strict bound
                flush(wm=max_t - source.disorder - 1)
        flush(wm=(max_t - source.disorder - 1)
              if max_t > -(2**62) else None)
        self.counts[source.name] = n

    def _consume_columnar(self, source: Source, parser: Parser) -> bool:
        """Columnar source protocol: ``iter_batches`` yields ``(t, k, s,
        d)`` arrays that go straight to the sink — no per-object Python.
        Only identity parsing qualifies (the arrays ARE the updates)."""
        batches = getattr(source, "iter_batches", lambda: None)()
        if batches is None or not isinstance(parser, IdentityParser):
            return False
        n = 0
        max_t = -(2**62)
        ctr = METRICS.events_ingested.labels(source.name)
        for t, k, s, d in batches:
            if self._stop.is_set():
                break
            if not len(t):
                continue
            n += len(t)
            max_t = max(max_t, int(np.max(t)))
            ctr.inc(len(t))
            self._sink_batch(source.name, t, k, s, d,
                             wm=max_t - source.disorder - 1)
        self.counts[source.name] = n
        return True

    def _consume_bulk(self, source: Source, parser: Parser) -> bool:
        """Native fast path: source exposes a byte buffer and the parser a
        C++ bulk tokeniser — one append_batch for the whole stream. Only
        taken when it preserves row-path semantics (the parser decides by
        returning None)."""
        read = getattr(source, "read_bytes", None)
        bulk = getattr(parser, "bulk_parse", None)
        if read is None or bulk is None:
            return False
        out = bulk(read())
        if out is None:
            return False
        t, k, s, d = out
        if len(t):
            METRICS.events_ingested.labels(source.name).inc(int(len(t)))
            self._sink_batch(source.name, t, k, s, d,
                             wm=int(t.max()) - source.disorder - 1)
        self.counts[source.name] = int(len(t))
        return True
